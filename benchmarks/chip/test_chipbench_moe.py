"""The DeepSeek-V2-Lite configuration against its plain reference on the
CPU, at a tiny size (fixtures/tiny-dsv2.*): the program's three checked
steps through the harness's own functions, the fp8 control, and the
per-layer readers of the MoE kernels."""

import json

import jax
import numpy as np
import pytest

import cells
import compare
import program
import run
import xtrace

FIXTURES = cells.HERE / "fixtures"
TRAFFIC = {"batch": 2, "seq": 32, "mesh": {"data": 1},
           "comm": {"mode": "mlsl", "wire": "bf16", "kv_chunk": 16}}
# CPU readings over seeds 1-4 and 3000000001: loss <= 4.5e-4, grad <=
# 7.1e-3, update <= 7.5e-3 (bfloat16 parameters and gradients against
# float32, and the routing flips that rounding makes at near-ties); the
# fp8 control reads loss >= 4.6e-3, grad >= 3.6e-2, update >= 1.4e-2
LIMITS = {"loss": 0.0015, "grad": 0.015, "update": 0.011}


@pytest.fixture(scope="module")
def cell():
    cfg = json.loads((FIXTURES / "tiny-dsv2.json").read_text())
    ref = cells.load_module(FIXTURES / "tiny-dsv2.py", "ref_tiny-dsv2")
    return cells.Cell(name="tiny-dsv2.b2", chips=1, cfg=cfg, ref=ref,
                      traffic=TRAFFIC, own={"limits": LIMITS},
                      end_to_end=[], per_layer=[])


def test_configuration_matches_the_program(cell):
    mc = program.model_config(cell.cfg)
    assert mc.moe.held == (4, 4) and mc.moe.n_experts == 16
    assert mc.lead_layers == ("mla",) and mc.pattern_repeats == 2


@pytest.mark.parametrize("seed", [1, 3000000001])
def test_program_matches_reference(cell, seed):
    """Loss, first gradient and the change after three steps of the
    program (dropless held-expert MoE, YaRN MLA, the flat mlsl engine)
    against the reference's masked-dense experts, on the same weights."""
    prog = program.Program(cell.cfg, cell.traffic,
                           cell.ref.param_specs(cell.cfg))
    state, _, fed, readings = run.checked_steps(prog, seed)
    program.free(state)
    ref = run.reference(cell, seed, fed, jax.devices()[:1])
    values = compare.numbers(readings, ref)
    assert compare.verdict(values, LIMITS), values
    assert all(x > 0 for x in readings["grad_norms"].values())


def test_fp8_control_is_not_correct(cell):
    seed = 2
    fed = program.fed_tokens(cell.cfg, cell.traffic, seed,
                             run.CHECKED_STEPS)
    devices = jax.devices()[:1]
    ref = run.reference(cell, seed, fed, devices)
    low = run.reference(cell, seed, fed, devices, fp8=True)
    assert not compare.verdict(compare.numbers(low, ref), LIMITS)


def test_flops_per_token_of_the_cell():
    """The cell's reckoning at 1 dense + 7 MoE layers: ~3.23 GFLOP a
    token, 2.23 TFLOP of routed expert matmuls a step (6144 expected rows
    a layer), 836.2M parameters."""
    c = cells.Bench().cell("dsv2lite-l7e8.moe-bf16.1chip")
    assert c.ref.flops_per_token(c.cfg, 4096) == pytest.approx(3.23e9,
                                                              rel=0.01)
    assert c.ref.expert_flops_per_step(c.cfg, c.tokens_per_step) == \
        pytest.approx(7 * 6144 * 2 * 3 * 2048 * 1408 * 3)
    n = sum(int(np.prod(s)) for s, _ in c.ref.param_specs(c.cfg).values())
    assert n == pytest.approx(836.2e6, rel=0.001)


def test_moe_readers():
    """moe.expert_ms sums the self time of the grouped-matmul kernels
    under whatever transformation names them; moe.gmm_roofline divides
    the least time of the running cell's routed expert matmuls by it, and
    says nothing where the run's facts match no cell that lists it."""
    V5E = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    c = cells.Bench().cell("dsv2lite-l7e8.moe-bf16.1chip")
    fpt = c.ref.flops_per_token(c.cfg, c.traffic["seq"])

    def ev(name, ts, dur, pid="/device:TPU:0", tid=xtrace.OPS_LINE):
        return {"pid": pid, "tid": tid, "name": name, "ts": ts, "dur": dur}
    host = dict(pid="/host:CPU", tid="python3")
    events = [ev(xtrace.WINDOW, 0, 100e6, **host),
              ev("bench/wait", 0, 50e6, **host),
              ev("bench/wait", 50e6, 50e6, **host),
              ev("%jvp_moe_gmm_.3 = bf16[8,8] custom-call()", 0, 4e6),
              ev("%transpose_jvp_moe_tgmm__.1 = bf16[8] custom-call()",
                 10e6, 6e6),
              ev("%fusion.2 = bf16[8] fusion()", 20e6, 30e6)]
    trace = xtrace.Trace(events)

    def facts(trace, **kw):
        return xtrace.Run(**{**dict(trace=trace, tokens_per_step=8192,
                                    chips=1, peak=V5E, flops_per_token=fpt,
                                    plan={}), **kw})
    load = lambda n: cells.load_module(           # noqa: E731
        cells.HERE / "metrics" / f"{n}.py", f"metric_{n}")
    assert load("moe.expert_ms").read(facts(trace)) == pytest.approx(5.0)
    roof = load("moe.gmm_roofline")
    flops = 7 * 6144 * 2 * 3 * 2048 * 1408 * 3
    assert roof.read(facts(trace)) == \
        pytest.approx(100 * flops / 197e12 / 5e-3)
    for other in ({"tokens_per_step": 4096}, {"chips": 4},
                  {"flops_per_token": fpt / 2}):
        assert roof.expert_flops(facts(trace, **other)) is None
        assert roof.read(facts(trace, **other)) is None
    quiet = facts(xtrace.Trace(events[:3] + events[-1:]))
    assert load("moe.expert_ms").read(quiet) is None
    assert roof.read(quiet) is None


def test_tiny_cell_runs_through_the_harness(bench_root, capsys):
    """The tiny configuration as a cell of its own (files and entries
    only), traced: the run is correct, and on the CPU, where the model's
    grouped matmuls are jax.lax.ragged_dot, the MoE kernels' readers find
    nothing and the line leaves their metrics out."""
    import shutil
    chip = bench_root / "benchmarks" / "chip"
    for name in ("tiny-dsv2.json", "tiny-dsv2.py"):
        shutil.copy(FIXTURES / name, chip / "configs" / name)
    (chip / "traffic" / "tiny-moe.json").write_text(json.dumps(TRAFFIC))
    (chip / "workloads" / "tiny-dsv2.b2.json").write_text(json.dumps(
        {"trace_steps": 3, "limits": LIMITS}))
    spec = json.loads((bench_root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny-dsv2", "source": "test",
                            "file": "benchmarks/chip/configs/tiny-dsv2.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tiny-dsv2.b2", "config": "tiny-dsv2",
                              "traffic": "tiny-moe", "chips": 1, "why": "t"})
    for m in spec["per_layer"]:
        # moe.gmm_roofline is the configuration's: it finds its cell
        # among those of the checkout's BENCHMARK.json
        if m["name"] != "moe.gmm_roofline":
            m["workloads"].append("tiny-dsv2.b2")
    (bench_root / "BENCHMARK.json").write_text(json.dumps(spec))
    rc = run.main(["--workload", "tiny-dsv2.b2", "--seed", "5", "--seconds",
                   "1", "--trace", "1"], root=bench_root, need_tpu=False)
    out, _ = capsys.readouterr()
    assert rc == 0
    res = json.loads(out.strip().splitlines()[-1])
    assert res["correct"] is True, res["checks"]
    assert "driver.host_ms" in res["metrics"]
    assert "moe.expert_ms" not in res["metrics"]
    assert "moe.gmm_roofline" not in res["metrics"]
