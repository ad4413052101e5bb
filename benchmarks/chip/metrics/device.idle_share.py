"""device.idle_share: 1 - (union of the device's op intervals / traced
window), averaged over the chips, in %."""


def read(run):
    t = run.trace
    if not t.devices or t.window_ns <= 0:
        return None
    busy = sum(t.busy_ns(pid) for pid in t.devices) / len(t.devices)
    return 100.0 * (1.0 - busy / t.window_ns)
