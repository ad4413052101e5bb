"""From the profiler's trace to the numbers the per-layer metrics report.

Two parts. `events` turns the profiler's xplane file into a plain list of
events (device ops and the harness's own host spans), which is all that a
metric sees and what the test fixture records. `Trace` and the functions
below reduce that list in pure Python: device busy time as a union of op
intervals, each op's self time (a loop's body nests inside the loop on the
ops line), idle gaps, op classes, and the host span that was open at a
given time.

On a TPU the trace names a device op by its HLO text
(`%fusion.12 = (...) fusion(...)`), and the profiler puts device ops and
host spans on one clock, in ns.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

WINDOW = "bench/window"        # the traced steps, on the host
HOST_SPANS = ("bench/data", "bench/dispatch", "bench/wait")
OPS_LINE = "XLA Ops"           # ops on the core
ASYNC_LINE = "Async XLA Ops"   # asynchronous copies and collectives
COLLECTIVES = ("all-reduce", "reduce-scatter", "all-gather", "all-to-all",
               "collective-permute")
# the Pallas kernels of kernels/quant8.py (the int8 wire), which the trace
# names after the jitted functions that call them
WIRE_KERNELS = ("quantize_blocks", "quantize_ef_blocks", "dequantize_blocks",
                "dequantize_accumulate_blocks")


def events(trace_dir: str) -> list:
    """The newest xplane file under `trace_dir` as a list of events:
    {"pid": plane, "tid": line, "name", "ts" (ns), "dur" (ns)}."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        return []
    data = ProfileData.from_file(max(files, key=os.path.getmtime))
    out = []
    for plane in data.planes:
        device = plane.name.startswith("/device:")
        if not device and not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if device or e.name.startswith("bench/"):
                    out.append({"pid": plane.name, "tid": line.name,
                                "name": e.name, "ts": float(e.start_ns),
                                "dur": float(e.duration_ns)})
    return out


def union(intervals) -> list:
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def length(intervals) -> float:
    return sum(e - s for s, e in union(intervals))


def minus(a, b) -> float:
    """Length of the union of `a` not covered by the union of `b`."""
    a, b = union(a), union(b)
    total, j = 0.0, 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        covered, k = 0.0, j
        while k < len(b) and b[k][0] < e:
            covered += min(e, b[k][1]) - max(s, b[k][0])
            k += 1
        total += (e - s) - covered
    return total


def op_name(ev) -> str:
    """A device op's HLO name without its instance number."""
    return re.sub(r"\.\d+$", "", ev["name"].split(" = ")[0].lstrip("%"))


def is_collective(ev) -> bool:
    return op_name(ev).startswith(COLLECTIVES)


def is_wire_kernel(ev) -> bool:
    return op_name(ev) in WIRE_KERNELS


def _self_times(ops: list) -> None:
    """Each op's time not covered by the ops nested in it, in e["self"]:
    the self times of one device's ops add up to its busy time. `ops` is
    sorted by start, an enclosing op first."""
    stack = []
    for e in ops:
        e["self"] = e["dur"]
        while stack and stack[-1]["ts"] + stack[-1]["dur"] <= e["ts"]:
            stack.pop()
        if stack:
            stack[-1]["self"] -= e["dur"]
        stack.append(e)


@dataclasses.dataclass
class Trace:
    """Device ops and host spans inside the traced window."""

    events: list

    def __post_init__(self):
        spans = [e for e in self.events if e["name"].startswith("bench/")]
        win = [e for e in spans if e["name"] == WINDOW]
        self.t0 = win[0]["ts"] if win else 0.0
        self.t1 = win[0]["ts"] + win[0]["dur"] if win else 0.0
        self.spans = [e for e in spans if e["name"] in HOST_SPANS
                      and self.t0 <= e["ts"] < self.t1]
        lines = {OPS_LINE: {}, ASYNC_LINE: {}}
        for e in self.events:
            if e["pid"].startswith("/device:") and e["tid"] in lines:
                s = max(e["ts"], self.t0)
                t = min(e["ts"] + e["dur"], self.t1)
                if t > s:
                    lines[e["tid"]].setdefault(e["pid"], []).append(
                        dict(e, ts=s, dur=t - s))
        order = lambda e: (e["ts"], -e["dur"])     # noqa: E731
        self.ops = {k: sorted(v, key=order)
                    for k, v in sorted(lines[OPS_LINE].items())}
        self.async_ops = {k: sorted(v, key=order)
                          for k, v in lines[ASYNC_LINE].items()}
        for ops in self.ops.values():
            _self_times(ops)

    @property
    def window_ns(self) -> float:
        return self.t1 - self.t0

    @property
    def devices(self) -> list:
        return list(self.ops)

    @property
    def steps(self) -> int:
        """Steps whose result arrived inside the window."""
        return sum(1 for e in self.spans if e["name"] == "bench/wait")

    def intervals(self, pid: str, keep=lambda ev: True) -> list:
        return [(e["ts"], e["ts"] + e["dur"]) for e in self.ops[pid]
                if keep(e)]

    def busy_ns(self, pid: str) -> float:
        return length(self.intervals(pid))

    def gaps(self, pid: str) -> list:
        """(start, end) of each interval of the window in which no op ran
        on device `pid`."""
        out, t = [], self.t0
        for s, e in union(self.intervals(pid)):
            if s > t:
                out.append((t, s))
            t = max(t, e)
        if self.t1 > t:
            out.append((t, self.t1))
        return out

    def host_span_at(self, t: float) -> str:
        """The innermost harness span open on the host at time t."""
        best = None
        for e in self.spans:
            if e["ts"] <= t < e["ts"] + e["dur"] and (
                    best is None or e["dur"] < best["dur"]):
                best = e
        return best["name"] if best else "bench/other"

    def span_ns(self, name: str) -> float:
        return sum(e["dur"] for e in self.spans if e["name"] == name)


def collective_intervals(trace: Trace, pid: str) -> list:
    """(start, end) of each collective on one device, from either ops line.
    An asynchronous one (`x-start.n` ... `x-done.n`) spans from its start
    to its done."""
    ops = sorted(trace.ops.get(pid, []) + trace.async_ops.get(pid, []),
                 key=lambda e: e["ts"])
    out, started = [], {}
    for e in ops:
        if not is_collective(e):
            continue
        name = e["name"].split(" = ")[0]
        if "-start" in name:
            started[name.replace("-start", "")] = e["ts"]
        elif "-done" in name and name.replace("-done", "") in started:
            out.append((started.pop(name.replace("-done", "")),
                        e["ts"] + e["dur"]))
        else:
            out.append((e["ts"], e["ts"] + e["dur"]))
    return out


@dataclasses.dataclass
class Run:
    """What a per-layer metric's reader gets: the trace of a short steady
    window and the facts of the cell it needs to turn time into a rate or a
    share."""

    trace: Trace
    tokens_per_step: int
    chips: int
    peak: dict | None          # peaks.json entry of the device, or None
    flops_per_token: float
    plan: dict                 # the comm engine's plan (program.Program.plan)

    @property
    def steps(self) -> int:
        return self.trace.steps


def device_ms(run: Run, keep) -> float | None:
    """Self time per step of the ops that `keep` selects, averaged over the
    chips, in ms; None where the trace has no device or no step."""
    t = run.trace
    if not t.devices or run.steps == 0:
        return None
    total = sum(e["self"] for pid in t.devices for e in t.ops[pid]
                if keep(e))
    return total / len(t.devices) / run.steps / 1e6


def breakdown(trace: Trace, top: int = 10) -> dict:
    """The device ops that took most time (self time in seconds per chip
    over the window, by HLO op) and the longest idle gaps, named by the
    harness span open on the host."""
    if not trace.devices:
        return {"device_ops": [], "idle_gaps": []}
    per = {}
    for pid in trace.devices:
        for e in trace.ops[pid]:
            per[op_name(e)] = per.get(op_name(e), 0.0) + e["self"]
    n = len(trace.devices)
    ops = sorted(per.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(trace.gaps(trace.devices[0]), key=lambda g: g[0] - g[1])
    return {
        "device_ops": [[k, v / n / 1e9] for k, v in ops],
        "idle_gaps": [[trace.host_span_at((s + e) / 2), (e - s) / 1e9]
                      for s, e in gaps[:top]],
    }
