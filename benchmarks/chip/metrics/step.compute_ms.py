"""step.compute_ms: device time per step of the ops that are neither
collectives nor the int8 wire kernels (model forward and backward, the
optimizer, casts and copies), averaged over the chips."""

import xtrace


def read(run):
    return xtrace.device_ms(run, lambda e: not xtrace.is_collective(e)
                            and not xtrace.is_wire_kernel(e))
