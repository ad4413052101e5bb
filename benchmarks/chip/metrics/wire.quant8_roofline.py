"""wire.quant8_roofline: least time of the int8 wire stage over the time
its quant8 kernels took (wire.kernel_ms), in %.

The least time is the bytes the stage must move on one chip at peak HBM
bandwidth, from the engine's plan (elements per bucket, route, chips per
level, error feedback) and not from how the kernels are written: the
gradient shard read in the bf16 wire dtype, the f32 error-feedback
residual read and written, the int8 message and its f32 block scales
written, then the gathered int8 message and scales read and the reduced
gradient written in bf16. Buckets that do not take the int8 wire (not
fusable) are not counted."""

import xtrace

GRAD, RESIDUAL, Q, SCALE, OUT = 2, 4, 1, 4, 2     # bytes per element


def stage_bytes(plan: dict) -> float:
    if plan.get("wire") != "int8":
        return 0.0
    per_scale = SCALE / plan["quant_block"]
    total = 0.0
    for b in plan["buckets"]:
        if not b["fusable"]:
            continue
        if b["route"] == "hier":
            gathered = b["elems"] / plan["n_local"]     # the fabric leg's input
            shard = gathered / plan["n_node"]
        else:
            gathered = b["elems"]
            shard = gathered / plan["dp"]
        quantize = shard * (GRAD + Q + per_scale)
        if plan["error_feedback"]:
            quantize += shard * 2 * RESIDUAL
        total += quantize + gathered * (Q + per_scale + OUT)
    return total


def read(run):
    ms = xtrace.device_ms(run, xtrace.is_wire_kernel)
    need = stage_bytes(run.plan)
    if not ms or not need or run.peak is None:
        return None
    return 100.0 * need / run.peak["hbm_bytes_per_s"] / (ms / 1e3)
