"""Chip benchmark of the MLSL training step: one cell, one run.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

A cell is an entry of BENCHMARK.json's `workloads`: a configuration
(configs/<config>.json, with its plain reference configs/<config>.py) under
a training job (traffic/<traffic>.json), with its own limits
(workloads/<cell>.json). The run:

1. set-up: builds the program's train step through its own functions
   (program.py), makes the weights on the device from the seed, and runs
   the three checked steps on the pipeline's batches through the window's
   own feed and call. They compile the step and warm it up; their losses,
   the first gradient and the change after three steps are read for the
   comparison;
2. the window: trains for --seconds with one step in flight (dispatch step
   k+1, then read step k's loss); batch generation runs inside it. With
   --trace 1 a few steps run under the profiler instead, and the per-layer
   metrics are read from that trace (metrics/<name>.py);
3. the check: reads the peak memory, frees the program's state, runs the
   plain reference's three steps (plain.py) and compares (compare.py).

The last line of stdout is one JSON object (`correct`, `attempted`,
`failed`, `metrics`, `device`, with --trace 1 `breakdown`, and last the
numbers compared with their limits under `checks`); the same numbers are
the last lines of stderr. Exits non-zero with no result when JAX finds no
TPU, fewer chips than the cell asks for, or a device_kind missing from
peaks.json.
"""

import time

T_START = time.perf_counter()      # set-up is timed from here

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
if str(HERE) not in sys.path:
    sys.path.insert(0, str(HERE))

import cells  # noqa: E402
import compare  # noqa: E402
import xtrace  # noqa: E402

CHECKED_STEPS = 3


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def say(msg: str) -> None:
    print(f"[bench {time.perf_counter() - T_START:8.3f}s] {msg}",
          file=sys.stderr, flush=True)


def compile_cache(root: pathlib.Path) -> None:
    """JAX's persistent cache in $JAX_COMPILATION_CACHE_DIR, else at a fixed
    place in the checkout; every program, however quick to compile."""
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("JAX_COMPILATION_CACHE_DIR")
                      or str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


# --- the three phases --------------------------------------------------------------

def checked_steps(prog, seed: int):
    """Set-up's steps: the state from the seed, then CHECKED_STEPS steps on
    the pipeline's batches. Returns (state, data config, host batches fed,
    the program's readings for the comparison)."""
    import jax
    import program
    state = prog.init_state(seed)
    jax.block_until_ready(state)
    say("state built")
    dcfg = program.data_config(prog.cfg, prog.traffic, seed)
    fed, losses = [], []
    for s in range(CHECKED_STEPS):
        tokens, batch = prog.feed(dcfg, s)
        state, metrics = prog.step(state, batch)
        losses.append(float(metrics["loss"]))
        fed.append(tokens)
        if s == 0:
            grad_norms = prog.first_grad_norms(state)
            say("first step done")
    readings = {"losses": losses, "grad_norms": grad_norms,
                "change_norms": prog.change_norms(state, seed),
                "residual_norms": prog.residual_norms(state)}
    return state, dcfg, fed, readings


def window(prog, state, dcfg, *, seconds=None, steps=None):
    """Train with one step in flight: dispatch step k+1, then read step k's
    loss. Runs for `seconds` (dispatching while time is left) or `steps`
    steps. Returns (state, t0, host time each step's result arrived,
    losses)."""
    import jax
    span = jax.profiler.TraceAnnotation
    s = first = CHECKED_STEPS
    done, losses, pending, marks = [], [], None, []
    with span(xtrace.WINDOW):
        t0 = time.perf_counter()
        while True:
            nxt, mark = None, [time.perf_counter()]
            if (time.perf_counter() - t0 < seconds if steps is None
                    else s - first < steps):
                with span("bench/data"):
                    _, batch = prog.feed(dcfg, s)
                mark.append(time.perf_counter())
                with span("bench/dispatch"):
                    state, nxt = prog.step(state, batch)
                mark.append(time.perf_counter())
                s += 1
            if pending is not None:
                with span("bench/wait"):
                    losses.append(float(pending["loss"]))
                done.append(time.perf_counter())
                marks.append(mark + [done[-1]])
            if nxt is None:
                longest(t0, done, marks)
                return state, t0, done, losses
            pending = nxt


def longest(t0, done, marks) -> None:
    """Says where the host spent the window's longest step: making the
    batch, dispatching the next step, waiting for this one's result."""
    gaps = [b - a for a, b in zip([t0] + done[:-1], done)]
    k = max(range(len(gaps)), key=gaps.__getitem__)
    m = marks[k]
    phases = ("data", "dispatch", "wait") if len(m) == 4 else ("wait",)
    parts = ", ".join(f"{n} {(b - a) * 1e3:.1f}" for n, a, b in
                      zip(phases, m, m[1:]))
    say(f"longest step {gaps[k] * 1e3:.1f} ms (step {k + 1} of "
        f"{len(gaps)}, its result at {done[k] - T_START:.3f} s): {parts} "
        f"ms")


def reference(cell, seed: int, fed: list, devices, *, fp8: bool = False):
    import plain
    return plain.train(cell.ref, cell.cfg, seed, fed, plain.Matmul(fp8=fp8),
                       plain.Wire(cell.traffic["comm"]), devices)


def device_peak_bytes(devices) -> int:
    """The fullest chip's peak: the runtime's peak of buffers in use plus
    the scratch it has reserved for the compiled programs' temporaries,
    which `peak_bytes_in_use` leaves out."""
    stats = [d.memory_stats() or {} for d in devices]
    return max(s.get("peak_bytes_in_use", 0) + s.get("peak_bytes_reserved", 0)
               for s in stats)


def end_to_end(cell, setup_s, t0, done, peak_bytes) -> dict:
    times_ms = [(b - a) * 1e3 for a, b in zip([t0] + done[:-1], done)]
    values = {
        "setup_s": setup_s,
        "tokens_per_s": len(done) * cell.tokens_per_step / (done[-1] - t0),
        "step_ms.p90": statistics.quantiles(times_ms, n=10,
                                            method="inclusive")[8],
        "peak_hbm_gb": peak_bytes / 1e9,
    }
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end}


def per_layer(cell, events: list, peak, plan: dict) -> tuple:
    trace = xtrace.Trace(events)
    facts = xtrace.Run(trace=trace, tokens_per_step=cell.tokens_per_step,
                       chips=cell.chips, peak=peak,
                       flops_per_token=cell.ref.flops_per_token(
                           cell.cfg, cell.traffic["seq"]),
                       plan=plan)
    metrics = {}
    for m, reader in cell.per_layer:
        value = reader.read(facts)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    busy = (sum(trace.busy_ns(p) for p in trace.devices)
            / len(trace.devices) / 1e9 if trace.devices else 0.0)
    return metrics, busy, trace.window_ns / 1e9, xtrace.breakdown(trace)


def run_cell(cell, args, peak, jax_devices) -> dict:
    import jax
    import program

    prog = program.Program(cell.cfg, cell.traffic,
                           cell.ref.param_specs(cell.cfg))
    devices = list(prog.mesh.devices.flat)
    say(f"{cell.name}: step built on {len(devices)} chip(s), seed "
        f"{args.seed}")
    state, dcfg, fed, readings = checked_steps(prog, args.seed)
    compiles = prog.step._cache_size()
    setup_s = time.perf_counter() - T_START
    say(f"set-up done; losses {readings['losses']}")

    extra = {}
    if args.trace:
        trace_dir = tempfile.mkdtemp(prefix="chipbench-")
        try:
            jax.profiler.start_trace(trace_dir)
            try:
                state, t0, done, losses = window(
                    prog, state, dcfg, steps=cell.own["trace_steps"])
            finally:
                jax.profiler.stop_trace()
            events = xtrace.events(trace_dir)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        state, t0, done, losses = window(prog, state, dcfg,
                                         seconds=args.seconds)
    if prog.step._cache_size() != compiles:
        raise RuntimeError("the step compiled inside the window")
    peak_bytes = device_peak_bytes(devices)
    plan = prog.plan()
    program.free(state)
    del state
    say(f"window: {len(done)} steps in {done[-1] - t0:.3f} s")

    if args.trace:
        metrics, busy_s, window_s, brk = per_layer(cell, events, peak, plan)
        extra["breakdown"] = brk
    else:
        metrics = end_to_end(cell, setup_s, t0, done, peak_bytes)
    ref = reference(cell, args.seed, fed, devices)
    say("reference done")
    values = compare.numbers(readings, ref)
    limits = cell.own["limits"]
    failed = sum(1 for x in losses if not math.isfinite(x))
    device = {"platform": jax_devices[0].platform,
              "kind": jax_devices[0].device_kind,
              "count": len(jax_devices), "memory_peak_bytes": peak_bytes}
    if args.trace:
        device.update(busy_s=busy_s, window_s=window_s)
    for k in values:
        if k not in limits:
            say(f"{k} {values[k]!r} (not compared in this cell)")
    return {"correct": compare.verdict(values, limits) and failed == 0,
            "attempted": len(done), "failed": failed, "metrics": metrics,
            "device": device, **extra,
            "checks": {k: {"value": values[k], "limit": limits[k]}
                       for k in limits}}


def main(argv=None, *, root: pathlib.Path = cells.ROOT,
         need_tpu: bool = True, fault=None) -> int:
    """`need_tpu=False` and `fault` are for the harness's own tests on the
    CPU: the first skips the look for a chip, the second breaks the
    program underneath (program.planted)."""
    args = parse(argv)
    try:
        bench = cells.Bench(root)
        cell = bench.cell(args.workload)
    except (cells.SpecError, KeyError, ValueError) as e:
        say(f"error: {e}")
        return 2
    src = root / "src"
    if not (src / "repro").is_dir():
        say(f"error: the program is not in {src}")
        return 2
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))

    import jax
    compile_cache(root)
    say("jax imported")
    devices = jax.devices()
    say(f"{len(devices)} {devices[0].platform} device(s)")
    if need_tpu and devices[0].platform != "tpu":
        say(f"error: needs a TPU; JAX found {devices[0].platform}")
        return 1
    if len(devices) < cell.chips:
        say(f"error: {cell.name} needs {cell.chips} chips; JAX found "
            f"{len(devices)}")
        return 1
    try:
        peak = bench.peak(devices[0].device_kind)
    except cells.SpecError as e:
        if need_tpu:
            say(f"error: {e}")
            return 1
        peak = None

    import program
    with program.planted(fault):
        out = run_cell(cell, args, peak, devices)
    for k, v in out["checks"].items():
        print(f"check {k} {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
