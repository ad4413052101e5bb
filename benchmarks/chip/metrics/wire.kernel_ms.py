"""wire.kernel_ms: device time per step of the quant8 Pallas kernels
(kernels/quant8.py via kernels/ops.py), averaged over the chips."""

import xtrace


def read(run):
    ms = xtrace.device_ms(run, xtrace.is_wire_kernel)
    return ms or None
