"""The harness end to end on the CPU at a tiny size: the last line's
schema, the refusals, a cell / configuration / metric added as new files
only, and `correct` coming out false under each planted fault and for the
fp8 control."""

import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

import compare
import run

HERE = pathlib.Path(run.__file__).resolve().parent


def main(root, cell, capsys, trace=0, fault=None, seed=3000000001):
    rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                   "1", "--trace", str(trace)], root=root, need_tpu=False,
                  fault=fault)
    out, err = capsys.readouterr()
    return rc, out, err


def last_line(out):
    return json.loads(out.strip().splitlines()[-1])


def test_result_line_schema(bench_root, capsys):
    rc, out, err = main(bench_root, "tiny.b2", capsys)
    assert rc == 0
    res = last_line(out)
    assert list(res) == ["correct", "attempted", "failed", "metrics",
                         "device", "checks"]
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert set(res["metrics"]) == {"tokens_per_s", "step_ms.p90",
                                   "peak_hbm_gb", "setup_s"}
    assert all(m["value"] > 0 for k, m in res["metrics"].items()
               if k != "peak_hbm_gb")
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        res["device"])
    assert set(res["checks"]) == {"loss", "grad", "update", "residual"}
    # the numbers compared are the last lines of stderr too
    tail = err.strip().splitlines()[-4:]
    assert [t.split()[1] for t in tail] == ["loss", "grad", "update",
                                            "residual"]


def test_traced_run_reads_the_per_layer_metrics(bench_root, capsys):
    rc, out, _ = main(bench_root, "tiny.b2", capsys, trace=1)
    assert rc == 0
    res = last_line(out)
    assert list(res)[-2:] == ["breakdown", "checks"]
    assert res["correct"] is True
    assert "driver.host_ms" in res["metrics"]
    assert {"busy_s", "window_s"} <= set(res["device"])


def test_added_files_are_found_and_run(bench_root, capsys):
    """A new configuration, job, cell and per-layer metric as files and
    BENCHMARK.json entries only."""
    chip = bench_root / "benchmarks" / "chip"
    shutil.copy(chip / "configs" / "tiny-llama.json",
                chip / "configs" / "tiny-wide.json")
    cfg = json.loads((chip / "configs" / "tiny-wide.json").read_text())
    cfg["name"] = "tiny-wide"
    cfg["intermediate_size"] = 192
    cfg["program"]["replace"]["d_ff"] = 192
    (chip / "configs" / "tiny-wide.json").write_text(json.dumps(cfg))
    shutil.copy(chip / "configs" / "tiny-llama.py",
                chip / "configs" / "tiny-wide.py")
    (chip / "traffic" / "tiny-b4.json").write_text(json.dumps(
        {"batch": 4, "seq": 32, "mesh": {"data": 1},
         "comm": {"mode": "mlsl", "wire": "bf16"}}))
    (chip / "workloads" / "tiny-wide.b4.json").write_text(json.dumps(
        {"trace_steps": 3, "limits": {"grad": 0.008}}))
    (chip / "metrics" / "driver.steps.py").write_text(
        "def read(run):\n    return float(run.steps)\n")
    spec = json.loads((bench_root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny-wide", "source": "test",
                            "file": "benchmarks/chip/configs/tiny-wide.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tiny-wide.b4", "config": "tiny-wide",
                              "traffic": "tiny-b4", "chips": 1, "why": "t"})
    spec["per_layer"].append({"name": "driver.steps", "unit": "steps",
                              "better": "higher", "source": "device_trace",
                              "layer": "driver", "moves": "tokens_per_s",
                              "workloads": ["tiny-wide.b4"]})
    (bench_root / "BENCHMARK.json").write_text(json.dumps(spec))
    rc, out, _ = main(bench_root, "tiny-wide.b4", capsys, trace=1)
    assert rc == 0
    res = last_line(out)
    assert res["metrics"]["driver.steps"]["value"] == 3.0
    assert list(res["checks"]) == ["grad"]


@pytest.mark.parametrize("cell,fault", [("tiny.b2", "frozen_state"),
                                        ("tiny.b2", "half_batch"),
                                        ("tiny.b2", "no_error_feedback"),
                                        ("tiny.hier", "half_batch"),
                                        ("tiny.hier", "no_exchange"),
                                        ("tiny.hier", "no_error_feedback")])
def test_planted_fault_is_not_correct(bench_root, capsys, cell, fault):
    rc, out, _ = main(bench_root, cell, capsys, fault=fault)
    assert rc == 0
    res = last_line(out)
    assert res["correct"] is False, res["checks"]


@pytest.mark.parametrize("seed", [1, 2])
def test_fp8_control_is_not_correct(bench_root, seed):
    """The reference in fp8 put in the program's place fails the cell's
    limits, against the float32 reference on the same weights and
    batches."""
    import program
    cell = run.cells.Bench(bench_root).cell("tiny.b2")
    fed = program.fed_tokens(cell.cfg, cell.traffic, seed, run.CHECKED_STEPS)
    import jax
    devices = jax.devices()[:1]
    ref = run.reference(cell, seed, fed, devices)
    low = run.reference(cell, seed, fed, devices, fp8=True)
    assert not compare.verdict(compare.numbers(low, ref),
                               cell.own["limits"])


def test_int8_wire_carries_its_rounding_error():
    """The reference's int8 wire: each 512-block rounded to 127 steps of
    its largest magnitude, and under error feedback what was sent plus
    the residual is every gradient so far."""
    import jax.numpy as jnp
    import numpy as np
    import plain
    rng = np.random.default_rng(0)
    g = [{"w": jnp.asarray(rng.normal(size=(3, 700)), jnp.float32)}
         for _ in range(2)]
    wire = plain.Wire({"wire": "int8", "error_feedback": True})
    res = wire.residuals(g[0])
    assert res["w"].shape == (5, 512)
    sent = []
    for gt in g:
        out, res = wire(gt, res)
        sent.append(out["w"])
    total = np.asarray(sent[0] + sent[1]) + np.asarray(
        res["w"]).reshape(-1)[:2100].reshape(3, 700)
    np.testing.assert_allclose(total, np.asarray(g[0]["w"] + g[1]["w"]),
                               rtol=0, atol=1e-5)
    block = np.asarray(g[0]["w"]).reshape(-1)[:512]
    step = np.abs(block).max() / 127
    first, _ = plain.Wire({"wire": "int8"})(g[0], None)
    np.testing.assert_allclose(np.asarray(first["w"]).reshape(-1)[:512],
                               np.round(block / step) * step, atol=1e-6)
    same, none = plain.Wire({"wire": "bf16"})(g[0], None)
    assert same is g[0] and none is None


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(HERE / "run.py"), "--workload",
         "yi6b-l2.int8ef.1chip", "--seed", "1", "--seconds", "1",
         "--trace", "0"], env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_benchmark_files_alone_no_result(tmp_path):
    """A checkout of only BENCHMARK.json and the harness: no program."""
    shutil.copytree(HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(HERE.parents[1] / "BENCHMARK.json", tmp_path)
    p = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "yi6b-l2.int8ef.1chip", "--seed", "1", "--seconds", "1"],
        cwd=tmp_path, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_unknown_device_kind_no_result(bench_root, capsys, monkeypatch):
    import jax

    class Chip:
        platform, device_kind = "tpu", "TPU v9 imaginary"

    monkeypatch.setattr(jax, "devices", lambda *a: [Chip()])
    rc = run.main(["--workload", "tiny.b2", "--seed", "1", "--seconds",
                   "1"], root=bench_root)
    out, err = capsys.readouterr()
    assert rc != 0 and out.strip() == ""
    assert "no peaks for device_kind" in err
