"""Shared set-up for the chip benchmark's own tests, which run on the CPU.

pytest collects `benchmarks/` before `tests/`, so this sets the same 8
virtual CPU devices as tests/conftest.py before anything imports jax, and
puts the program and the harness on the path.
"""

import os
import pathlib
import shutil
import sys

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
for p in (str(ROOT / "src"), str(HERE)):
    if p not in sys.path:
        sys.path.insert(0, p)
if "--xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                               + os.environ.get("XLA_FLAGS", ""))

import json  # noqa: E402

import pytest  # noqa: E402

FIXTURES = HERE / "fixtures"
# CPU-sized cells over the LLaMA reference: a flat int8 + EF job on one
# device and a two-level one on four
TINY_CELLS = [
    {"name": "tiny.b2", "config": "tiny-llama", "traffic": "tiny-b2",
     "chips": 1, "why": "CPU-sized flat int8 + EF job"},
    {"name": "tiny.hier", "config": "tiny-llama", "traffic": "tiny-hier-b8",
     "chips": 4, "why": "CPU-sized two-level int8 + EF job"},
]
# limits of the tiny cells, from CPU readings over seeds 1-8 (flat) and
# 1-6 (two-level): the program reads loss <= 6.3e-4, grad <= 2.7e-3,
# update <= 6.3e-3, residual <= 0.22 (the norm scales, 128 elements a
# layer, share their 512-element blocks with other leaves at these widths);
# the fp8 control grad >= 1.8e-2; the planted faults grad or update >= 0.1,
# residual >= 0.56 (no error feedback and a frozen state: 1)
TINY_LIMITS = {"loss": 0.003, "grad": 0.008, "update": 0.02,
               "residual": 0.45}

def add_tiny_cells(root: pathlib.Path) -> None:
    """Add the tiny configuration, jobs and cells to the benchmark copied
    under `root`, as files and entries only."""
    chip = root / "benchmarks" / "chip"
    for name in ("tiny-llama.json", "tiny-llama.py"):
        shutil.copy(FIXTURES / name, chip / "configs" / name)
    for name in ("tiny-b2.json", "tiny-hier-b8.json"):
        shutil.copy(FIXTURES / name, chip / "traffic" / name)
    for cell in TINY_CELLS:
        (chip / "workloads" / f"{cell['name']}.json").write_text(
            json.dumps({"trace_steps": 3, "limits": TINY_LIMITS}))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({
        "name": "tiny-llama", "source": "benchmarks/chip/fixtures",
        "file": "benchmarks/chip/configs/tiny-llama.json", "reduced": [],
        "why": "CPU-sized LLaMA block"})
    spec["workloads"] += TINY_CELLS
    for m in spec["per_layer"]:
        m["workloads"] += [c["name"] for c in TINY_CELLS]
    (root / "BENCHMARK.json").write_text(json.dumps(spec, indent=1))


@pytest.fixture
def bench_root(tmp_path, monkeypatch):
    """A checkout with a copy of the benchmark plus the tiny cells, the
    program linked in, and JAX's compile cache kept inside it for the
    test's length."""
    import jax
    root = tmp_path / "checkout"
    shutil.copytree(HERE, root / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__", "fixtures",
                                                  "test_*", "conftest.py"))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    (root / "src").symlink_to(ROOT / "src")
    add_tiny_cells(root)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    saved = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs")}
    yield root
    for k, v in saved.items():
        jax.config.update(k, v)
    from jax.experimental.compilation_cache import compilation_cache
    compilation_cache.reset_cache()
