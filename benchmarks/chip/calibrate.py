"""Readings that the comparison's limits are set from (run on the chip).

    python3 benchmarks/chip/calibrate.py --workload <cell> --seeds 1,2,... \\
        [--control 1,2,3] [--fault half_batch=1,2,3] [--trace-out FILE]

In one process, on the cell's own sizes: for each of --seeds, the
program's three checked steps against the reference's (the lower
readings); for each of --control, the reference computed with fp8
products against the reference (the control, which has to fail); for each
--fault, the program with that fault planted (program.planted) against the
reference. Prints one JSON line per reading. --trace-out writes the event
list of one traced window (xtrace.events) after the first seed, for the
tests' trace fixture. The benchmark's own runs never run this.
"""

import argparse
import json
import pathlib
import shutil
import sys
import tempfile
import time

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import run  # noqa: E402


def seeds(text: str) -> list:
    return [int(s) for s in text.split(",") if s]


def memory_report(prog, state, dcfg, stats) -> None:
    """The runtime's memory counters on each chip beside what the
    compiled step plans, as one JSON line."""
    _, batch = prog.feed(dcfg, 0)
    plan = prog.step.lower(state, batch).compile().memory_analysis()
    print(json.dumps({
        "kind": "memory", "memory_stats": stats,
        "compiled": {k: getattr(plan, k) for k in dir(plan)
                     if k.endswith("_in_bytes")}}), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds, default=[])
    ap.add_argument("--control", type=seeds, default=[])
    ap.add_argument("--fault", action="append", default=[],
                    help="name=seed,seed,...")
    ap.add_argument("--trace-out", default=None)
    args = ap.parse_args(argv)

    root = run.cells.ROOT
    sys.path.insert(0, str(root / "src"))
    import jax
    import program
    run.compile_cache(root)
    cell = run.cells.Bench(root).cell(args.workload)
    specs = cell.ref.param_specs(cell.cfg)
    refs = {}

    def emit(kind, seed, readings, ref, **kw):
        """One reading: the numbers compared, and where each is worst."""
        gaps = run.compare.gaps(readings, ref)
        worst = {k: max(v, key=v.get) for k, v in gaps.items()}
        print(json.dumps({
            "cell": cell.name, "kind": kind, "seed": seed,
            **{k: v[worst[k]] for k, v in gaps.items()}, "worst": worst,
            "losses": readings["losses"], "ref_losses": ref["losses"],
            "loss_steps": gaps["loss"],
            "leaves": len(gaps["grad"]), "moved": len(gaps["update"]),
            "worst_norms": {
                k: [(readings[f"{src}_norms"] or {}).get(worst[k], 0.0),
                    ref[f"{src}_norms"][worst[k]]]
                for k, src in (("grad", "grad"), ("update", "change"),
                               ("residual", "residual")) if k in worst},
            **kw}), flush=True)

    def program_readings(fault, seed_list, trace_out=None):
        with program.planted(fault):
            prog = program.Program(cell.cfg, cell.traffic, specs)
            devices = list(prog.mesh.devices.flat)
            for seed in seed_list:
                state, dcfg, fed, readings = run.checked_steps(prog, seed)
                if trace_out:
                    trace_dir = tempfile.mkdtemp(prefix="chipbench-")
                    jax.profiler.start_trace(trace_dir)
                    state, *_ = run.window(prog, state, dcfg,
                                           steps=cell.own["trace_steps"])
                    jax.profiler.stop_trace()
                    with open(trace_out, "w") as f:
                        json.dump(run.xtrace.events(trace_dir), f)
                    shutil.rmtree(trace_dir, ignore_errors=True)
                    trace_out = None
                stats = [d.memory_stats() or {} for d in devices]
                peak = run.device_peak_bytes(devices)
                if fault is None and seed == seed_list[0]:
                    memory_report(prog, state, dcfg, stats)
                program.free(state)
                del state
                t0 = time.perf_counter()
                if seed not in refs:
                    refs[seed] = run.reference(cell, seed, fed, devices)
                emit(fault or "program", seed, readings, refs[seed],
                     peak_bytes=peak, ref_s=time.perf_counter() - t0)
        return devices, fed

    devices, _ = program_readings(None, args.seeds, args.trace_out)
    for spec in args.fault:
        name, _, seed_text = spec.partition("=")
        program_readings(name, seeds(seed_text))
    for seed in args.control:
        fed = program.fed_tokens(cell.cfg, cell.traffic, seed,
                                 run.CHECKED_STEPS)
        if seed not in refs:
            refs[seed] = run.reference(cell, seed, fed, devices)
        low = run.reference(cell, seed, fed, devices, fp8=True)
        emit("control_fp8", seed, low, refs[seed])
    return 0


if __name__ == "__main__":
    sys.exit(main())
