"""comm.exposed_ms: the part of comm.collective_ms during which no other op
runs on that chip, per step, on the chip where it is largest."""

import xtrace


def read(run):
    t = run.trace
    if not t.devices or run.steps == 0:
        return None
    exposed = []
    for pid in t.devices:
        coll = xtrace.collective_intervals(t, pid)
        if coll:
            other = t.intervals(pid, lambda e: not xtrace.is_collective(e))
            exposed.append(xtrace.minus(coll, other))
    return max(exposed) / run.steps / 1e6 if exposed else None
