"""Observability stack (repro.obs): trace writer, CommStats, step meter.

The PR-9 acceptance claims verified here:

  * the Chrome-trace writer round-trips through write/load/validate and
    nested host spans stay containment-nested;
  * ``CommEngine.stats()`` wire bytes exactly match the plan's message
    sizes x wire widths — flat fp32 is ``n_elems * 4`` unpadded, the
    hierarchical int8 fabric gather leg is ``elems * 1`` plus one f32
    scale per QUANT_BLOCK;
  * every stats/meter ledger entry is warn-only by construction
    (informational or unstable) so the perf diff gate cannot trip on it;
  * a mesh8 engine's stats/table/describe agree with the plan.
"""

import json

import jax
import pytest

from repro.core import collectives as cl
from repro.core import engine as eng
from repro.core import hier, planner
from repro.obs import meter as obs_meter
from repro.obs import stats as obs_stats
from repro.obs import trace as obs_trace

DATA_AXES = (hier.NODE_AXIS, hier.LOCAL_AXIS)


def _tree():
    k = jax.random.PRNGKey(3)
    return {"embed": jax.random.normal(k, (32, 8)),
            "w": jax.random.normal(jax.random.fold_in(k, 1), (64, 16)),
            "head": jax.random.normal(jax.random.fold_in(k, 2), (8, 32))}


# --------------------------------------------------------------------------
# trace writer
# --------------------------------------------------------------------------

def test_trace_round_trip(tmp_path):
    w = obs_trace.TraceWriter()
    w.name_process(0, "measured")
    w.name_thread(0, 0, "steps")
    w.complete("step0", 0.0, 100.0, pid=0, tid=0, cat="step",
               args={"loss": 1.0})
    w.instant("ckpt", 50.0)
    path = w.write(str(tmp_path / "trace.json"))
    obj = obs_trace.load_trace(path)
    assert obj["displayTimeUnit"] == "ms"
    names = [e["name"] for e in obj["traceEvents"]]
    assert "step0" in names and "ckpt" in names
    x = next(e for e in obj["traceEvents"] if e["name"] == "step0")
    assert x["ph"] == "X" and x["dur"] == 100.0 and x["args"]["loss"] == 1.0


def test_trace_span_nesting():
    """Host spans nest by containment: inner X interval inside outer's."""
    w = obs_trace.TraceWriter()
    with w.span("outer", cat="step"):
        with w.span("inner", cat="comm"):
            pass
    by_name = {e["name"]: e for e in w.events}
    outer, inner = by_name["outer"], by_name["inner"]
    assert inner["ts"] >= outer["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-6
    obs_trace.validate_trace(w.to_json())


def test_trace_metadata_dedup_and_negative_dur():
    w = obs_trace.TraceWriter()
    w.name_process(1, "modeled")
    w.name_process(1, "modeled again")          # deduped
    assert sum(e["ph"] == "M" for e in w.events) == 1
    w.complete("clamp", 10.0, -5.0)             # clamped, never invalid
    assert w.events[-1]["dur"] == 0.0
    obs_trace.validate_trace(w.to_json())


def test_validate_trace_rejects_malformed():
    with pytest.raises(ValueError):
        obs_trace.validate_trace({"traceEvents": "nope"})
    with pytest.raises(ValueError):
        obs_trace.validate_trace(
            {"traceEvents": [{"ph": "X", "name": "a", "ts": 0.0}]})  # no dur
    with pytest.raises(ValueError):
        obs_trace.validate_trace(
            {"traceEvents": [{"ph": "B", "name": "a", "ts": 0.0}]})  # no E


def test_trace_counter_round_trip(tmp_path):
    """Counter ("C") events — the tok/s / exposed-share rate tracks — write,
    load, and validate; malformed counters are rejected."""
    w = obs_trace.TraceWriter()
    w.counter("rates", 10.0, {"tokens_per_sec": 123.0,
                              "exposed_comm_share": 0.25})
    w.counter("rates", 20.0, {"tokens_per_sec": 130.0,
                              "exposed_comm_share": 0.20})
    path = w.write(str(tmp_path / "trace.json"))
    obj = obs_trace.load_trace(path)
    cs = [e for e in obj["traceEvents"] if e["ph"] == "C"]
    assert len(cs) == 2
    assert cs[0]["args"]["tokens_per_sec"] == 123.0
    assert cs[1]["args"]["exposed_comm_share"] == 0.20
    for bad in (
        {"ph": "C", "name": "r", "ts": 0.0},                    # no args
        {"ph": "C", "name": "r", "ts": 0.0, "args": {}},        # empty
        {"ph": "C", "name": "r", "ts": 0.0, "args": {"x": "y"}},  # non-num
    ):
        with pytest.raises(ValueError):
            obs_trace.validate_trace({"traceEvents": [bad]})


# --------------------------------------------------------------------------
# CommStats wire-byte math
# --------------------------------------------------------------------------

def test_flat_fp32_bytes_exact(mesh8):
    plan = eng.build_plan(_tree(), eng.CommConfig(mode="mlsl", wire="fp32"),
                          mesh8, DATA_AXES)
    st = obs_stats.CommStats.from_plan(plan)
    assert len(st.buckets) == plan.n_buckets
    for b in st.buckets:
        # flat float allreduce: one unpadded message, width 4
        assert b.route == planner.ALGO_FLAT
        assert b.total_bytes == b.n_elems * 4
        assert b.intra_bytes == 0 and b.pad_frac == 0.0


def test_hier_int8_leg_bytes_exact(mesh8):
    comm = eng.CommConfig(mode="mlsl", wire="int8", hier=True,
                          error_feedback=True)
    plan = eng.build_plan(_tree(), comm, mesh8, DATA_AXES)
    st = obs_stats.CommStats.from_plan(plan)
    hier_rows = [b for b in st.buckets if b.route == planner.ALGO_HIER]
    assert hier_rows, "hier plan must route fusable buckets two-level"
    for b in hier_rows:
        rs_i, rs_f, ag_f, ag_i = b.legs
        padded = rs_i.elems
        assert padded % hier._pad_quantum(plan.n_local, plan.n_node,
                                          cl.WIRE_INT8) == 0
        m = padded // plan.n_local
        # intra legs: bf16 (lossy fabric => bf16 intra default), full volume
        assert rs_i.level == ag_i.level == "intra"
        assert rs_i.payload_bytes == ag_i.payload_bytes == padded * 2
        # fabric RS rides bf16: 2 bytes/elem of the 1/local message
        assert rs_f.level == "inter" and rs_f.payload_bytes == 2 * m
        # fabric AG is the int8 wire: 1 byte/elem + one f32 scale per block
        assert ag_f.level == "inter" and ag_f.wire == cl.WIRE_INT8
        assert ag_f.payload_bytes == m * 1
        assert ag_f.scale_bytes == m // cl.QUANT_BLOCK * 4
        assert ag_f.total_bytes == m + m // cl.QUANT_BLOCK * 4
        assert b.ef


def test_nonfusable_falls_back_flat_float(mesh8):
    comm = eng.CommConfig(mode="mlsl", wire="int8", hier=True)
    plan = eng.build_plan(_tree(), comm, mesh8, DATA_AXES,
                          leaf_replicated=lambda path: False)
    st = obs_stats.CommStats.from_plan(plan)
    assert all(not b.fusable for b in st.buckets)
    for b in st.buckets:
        # reduce_chained reduces non-fusable buckets per-leaf on the bf16
        # fallback wire, flat — the stats must mirror that exactly
        assert b.route == planner.ALGO_FLAT and b.wire == cl.WIRE_BF16
        assert b.total_bytes == b.n_elems * 2 and not b.ef


def test_stats_metrics_warn_only(mesh8):
    comm = eng.CommConfig(mode="mlsl", wire="int8", hier=True)
    plan = eng.build_plan(_tree(), comm, mesh8, DATA_AXES)
    ms = obs_stats.CommStats.from_plan(plan, measured=(1e-3,) *
                                       plan.n_buckets).to_metrics()
    assert ms
    for m in ms:
        assert m["better"] is None or m["stable"] is False, m
    names = {m["name"] for m in ms}
    assert "comm_stats/total/total_B" in names
    assert any(n.endswith("/t_measured_us") for n in names)


# --------------------------------------------------------------------------
# engine integration (mesh8)
# --------------------------------------------------------------------------

def test_engine_stats_and_describe(mesh8):
    comm = eng.CommConfig(mode="mlsl", wire="int8", hier=True,
                          topo="xeon-shm-10gbe")
    engine = eng.CommEngine.create(_tree(), comm, mesh8, DATA_AXES)
    st = engine.stats()
    assert len(st.buckets) == engine.plan.n_buckets
    assert st.topo_name == "xeon-shm-10gbe"    # plan's routing topo reused
    assert all(b.t_model is not None and b.t_model > 0 for b in st.buckets)
    table = st.table()
    # one row per bucket + header/sum; describe() is the same table
    assert all(f"\n  {b.index}  " in table or f"\n{b.index}  " in table
               or str(b.n_elems) in table for b in st.buckets)
    assert engine.plan.describe().splitlines()[0] == table.splitlines()[0]


def test_measure_bucket_times_smoke(mesh8):
    comm = eng.CommConfig(mode="mlsl", wire="int8", hier=True,
                          error_feedback=True)
    engine = eng.CommEngine.create(_tree(), comm, mesh8, DATA_AXES)
    with jax.set_mesh(mesh8):
        times = obs_stats.measure_bucket_times(engine, mesh8, iters=1,
                                               warmup=1)
    assert len(times) == engine.plan.n_buckets
    assert all(t > 0 for t in times)
    st = engine.stats(measured=times)
    assert st.t_measured_total == pytest.approx(sum(times))


def test_bucket_timer_compile_once_sample_many(mesh8):
    """The telemetry loop's sampled replay: BucketTimer compiles each
    bucket's region once, then repeated sample() calls stay cheap and keep
    producing a full positive per-bucket vector."""
    import time as _time
    comm = eng.CommConfig(mode="mlsl", wire="int8", hier=True)
    engine = eng.CommEngine.create(_tree(), comm, mesh8, DATA_AXES)
    with jax.set_mesh(mesh8):
        timer = engine.bucket_timer(mesh8)
        first = timer.sample(warmup=1)           # pays the compiles
        t0 = _time.perf_counter()
        second = timer.sample()
        resample_s = _time.perf_counter() - t0
    assert len(first) == len(second) == engine.plan.n_buckets
    assert all(t > 0 for t in first) and all(t > 0 for t in second)
    # post-compile sampling must be far below any training-step timescale
    assert resample_s < 5.0


# --------------------------------------------------------------------------
# step meter
# --------------------------------------------------------------------------

def test_meter_ema_bias_correction():
    m = obs_meter.StepMeter(ema_decay=0.9, tokens_per_step=100)
    m.update(dt=0.5)
    # after one step the bias-corrected EMA IS the observation
    assert m.step_time == pytest.approx(0.5)
    for _ in range(200):
        m.update(dt=0.5)
    assert m.step_time == pytest.approx(0.5)
    assert m.tokens_per_sec == pytest.approx(200.0)


def test_meter_exposed_frac_and_metrics():
    m = obs_meter.StepMeter()
    assert m.exposed_comm_frac is None
    m.update(dt=0.1, loss=2.0, grad_norm=1.5)
    m.exposed_comm_model = 0.02
    assert m.exposed_comm_frac == pytest.approx(0.2)
    m.exposed_comm_model = 1e9            # model overestimate: capped
    assert m.exposed_comm_frac == 1.0
    assert "loss 2.0000" in m.summary()
    for entry in m.to_metrics():
        assert entry["stable"] is False
    with pytest.raises(ValueError):
        obs_meter.StepMeter().update()    # update without start()


def test_meter_ledger_compatible(tmp_path):
    """Meter + stats entries record cleanly into a schema-valid ledger."""
    from benchmarks import common as bench_common
    m = obs_meter.StepMeter(tokens_per_step=10)
    m.update(dt=0.01)
    led = bench_common.Ledger("obs_test")
    for entry in m.to_metrics():
        led.record(**entry)
    path = led.write(str(tmp_path))
    rec = json.load(open(path))
    bench_common.validate_ledger(rec)
    assert any(e["name"] == "meter/step_time_us" for e in rec["metrics"])
