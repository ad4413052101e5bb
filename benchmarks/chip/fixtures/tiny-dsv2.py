"""The plain reference of configs/dsv2lite-l7e8.py, for the CPU-sized
configuration beside it (tiny-dsv2.json): the same module, loaded from the
configurations' directory."""

import cells

_REF = cells.load_module(cells.HERE / "configs" / "dsv2lite-l7e8.py",
                         "ref_dsv2lite-l7e8")
param_specs = _REF.param_specs
loss = _REF.loss
flops_per_token = _REF.flops_per_token
expert_flops_per_step = _REF.expert_flops_per_step
