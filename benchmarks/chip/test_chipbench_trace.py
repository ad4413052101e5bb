"""The trace reduction (xtrace.py and metrics/) on hand-made events and on
two-step traces recorded on a TPU v5e and trimmed (fixtures/trace-*.json):
the four-chip two-level int8 + EF cell and the one-chip int8 + EF cell."""

import json

import pytest

import cells
import run
import xtrace

FIXTURES = cells.HERE / "fixtures"

V5E = {"bf16_flops": 197e12, "int8_ops": 393e12, "hbm_bytes_per_s": 819e9,
       "hbm_bytes": 16e9}


def ev(name, ts, dur, pid="/device:TPU:0", tid=xtrace.OPS_LINE):
    return {"pid": pid, "tid": tid, "name": name, "ts": ts, "dur": dur}


def window(t1, *spans):
    return [ev(xtrace.WINDOW, 0, t1, pid="/host:CPU", tid="python3")] + [
        ev(n, s, d, pid="/host:CPU", tid="python3") for n, s, d in spans]


def test_union_and_minus():
    assert xtrace.union([(5, 7), (0, 2), (1, 3)]) == [(0, 3), (5, 7)]
    assert xtrace.length([(0, 2), (1, 3), (5, 7)]) == 5
    assert xtrace.minus([(0, 10)], [(2, 3), (5, 6), (9, 12)]) == 7
    assert xtrace.minus([(0, 1), (4, 6)], []) == 3


def test_self_time_of_nested_ops_adds_up_to_busy_time():
    t = xtrace.Trace(window(100, ("bench/wait", 0, 100)) + [
        ev("%while.1 = (...) while(...)", 10, 50),
        ev("%fusion.2 = f32[8] fusion(...)", 12, 10),
        ev("%quantize_ef_blocks.3 = (...) custom-call(...)", 30, 20),
        ev("%all-reduce.4 = f32[8] all-reduce(...)", 70, 10)])
    pid = t.devices[0]
    selfs = {xtrace.op_name(e): e["self"] for e in t.ops[pid]}
    assert selfs == {"while": 20, "fusion": 10, "quantize_ef_blocks": 20,
                     "all-reduce": 10}
    assert sum(selfs.values()) == t.busy_ns(pid) == 60
    assert t.gaps(pid) == [(0, 10), (60, 70), (80, 100)]


def test_op_classes_and_async_collective_pairs():
    assert xtrace.is_wire_kernel(ev("%dequantize_blocks.7 = (...)", 0, 1))
    assert not xtrace.is_wire_kernel(ev("%fusion.7 = (...)", 0, 1))
    assert xtrace.is_collective(ev("%all-gather-start.2 = (...)", 0, 1))
    t = xtrace.Trace(window(100, ("bench/wait", 0, 100)) + [
        ev("%fusion.1", 0, 40),
        ev("%all-gather-start.2 = (...)", 10, 1, tid=xtrace.ASYNC_LINE),
        ev("%all-gather-done.2 = (...)", 50, 5),
        ev("%reduce-scatter.3 = (...)", 60, 10)])
    coll = xtrace.collective_intervals(t, t.devices[0])
    assert sorted(coll) == [(10, 55), (60, 70)]
    assert xtrace.minus(coll, t.intervals(
        t.devices[0], lambda e: not xtrace.is_collective(e))) == 25


def test_host_span_names_each_idle_gap():
    t = xtrace.Trace(window(100, ("bench/data", 0, 30),
                            ("bench/dispatch", 30, 10),
                            ("bench/wait", 40, 60)) + [ev("%fusion.1", 35, 50)])
    brk = xtrace.breakdown(t)
    assert brk["idle_gaps"] == [["bench/data", 35e-9], ["bench/wait", 15e-9]]
    assert brk["device_ops"] == [["fusion", 50e-9]]


def reduce(cell_name, fixture):
    cell = cells.Bench().cell(cell_name)
    events = json.loads((FIXTURES / fixture).read_text())
    plan = json.loads((FIXTURES / "plans.json").read_text())[cell_name]
    return run.per_layer(cell, events, V5E, plan)


def test_four_chip_trace():
    metrics, busy_s, window_s, brk = reduce(
        "yi6b-l2.hier-int8ef.4chip", "trace-hier-int8ef-4chip.json")
    v = {k: m["value"] for k, m in metrics.items()}
    assert set(v) == {"driver.host_ms", "device.idle_share",
                      "step.compute_ms", "step.mfu", "wire.kernel_ms",
                      "wire.quant8_roofline", "comm.collective_ms",
                      "comm.exposed_ms"}
    step_ms = window_s * 1e3 / 2
    # the step's self time splits into compute, wire kernels and collectives
    assert v["step.compute_ms"] + v["wire.kernel_ms"] \
        + v["comm.collective_ms"] == pytest.approx(step_ms, rel=0.01)
    assert 0 < v["comm.exposed_ms"] <= v["comm.collective_ms"]
    assert 0 < v["wire.quant8_roofline"] < 100
    assert 0 < v["step.mfu"] < 100
    assert 0 <= v["device.idle_share"] < 5
    assert 0 < busy_s <= window_s
    assert len(brk["device_ops"]) == len(brk["idle_gaps"]) == 10
    assert {"all-reduce", "quantize_ef_blocks"} <= {
        name for name, _ in brk["device_ops"]}


def test_one_chip_trace_has_no_collectives():
    metrics, busy_s, window_s, _ = reduce(
        "yi6b-l2.int8ef.1chip", "trace-int8ef-1chip.json")
    assert "comm.collective_ms" not in metrics
    assert "comm.exposed_ms" not in metrics
    v = {k: m["value"] for k, m in metrics.items()}
    assert v["step.compute_ms"] + v["wire.kernel_ms"] == pytest.approx(
        window_s * 1e3 / 2, rel=0.01)
    assert 0 < v["wire.quant8_roofline"] < 100


def test_no_device_plane_reads_nothing():
    t = xtrace.Trace(window(100, ("bench/wait", 0, 100)))
    run_ = xtrace.Run(trace=t, tokens_per_step=2048, chips=1, peak=V5E,
                      flops_per_token=1e9, plan={"buckets": []})
    for name in ("device.idle_share", "step.compute_ms", "wire.kernel_ms",
                 "wire.quant8_roofline", "comm.collective_ms"):
        reader = cells.load_module(cells.HERE / "metrics" / f"{name}.py",
                                   f"metric_{name}")
        assert reader.read(run_) is None, name
