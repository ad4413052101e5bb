"""The LLaMA reference of configs/yi6b-l2.py, for a CPU-sized
configuration in the harness's own tests."""

import pathlib

import cells

_yi = cells.load_module(pathlib.Path(cells.HERE) / "configs" / "yi6b-l2.py",
                        "ref_yi6b_l2_for_tests")
param_specs, loss, flops_per_token = (_yi.param_specs, _yi.loss,
                                      _yi.flops_per_token)
