"""moe.expert_ms: device self time per step of the dropless MoE's grouped
matmul kernels (kernels/gmm.py: `moe_gmm` for the forward products and the
rows' gradients, `moe_tgmm` for the weights' gradients), averaged over the
chips. The trace names each after the pallas_call's name, which a
transformation's prefix may surround (`jvp_moe_gmm_`)."""

import re

import xtrace

KERNELS = re.compile(r"moe_t?gmm")


def is_expert_kernel(ev) -> bool:
    return KERNELS.search(xtrace.op_name(ev)) is not None


def read(run):
    return xtrace.device_ms(run, is_expert_kernel) or None
