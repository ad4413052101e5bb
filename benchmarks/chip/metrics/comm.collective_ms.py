"""comm.collective_ms: device time per step of the collectives
(all-reduce, reduce-scatter, all-gather, all-to-all, collective-permute;
an asynchronous one from its start to its done) on the busiest chip."""

import xtrace


def read(run):
    t = run.trace
    if not t.devices or run.steps == 0:
        return None
    busiest = max(xtrace.length(xtrace.collective_intervals(t, pid))
                  for pid in t.devices)
    return busiest / run.steps / 1e6 or None
