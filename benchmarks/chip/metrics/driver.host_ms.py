"""driver.host_ms: host time per step spent making the batch
(`data/pipeline.py`, then `device_put`) and dispatching the step: the
harness's bench/data and bench/dispatch spans in the traced window."""


def read(run):
    if run.steps == 0:
        return None
    t = run.trace
    return (t.span_ns("bench/data") + t.span_ns("bench/dispatch")) \
        / run.steps / 1e6
