"""Observability: MLSL-style comm stats, a host trace writer, step meter.

MLSL's proof points (paper §4) are per-message statistics — bytes, algorithm,
exposed vs overlapped time — that only the library owning the exchange can
produce. This subpackage is that accounting layer for the reproduction:

  repro.obs.trace  -- Chrome-trace-event (Perfetto-compatible) writer with
                      host-side span helpers (the serving CLI's
                      timeline; training traces come from jax.profiler).
  repro.obs.stats  -- CommStats: the per-bucket wire-byte / route / modeled-
                      vs-measured-time report derived from an EnginePlan
                      (surfaced as EnginePlan.describe() / CommEngine.stats()
                      and serialized into the perf-ledger schema).
  repro.obs.meter  -- StepMeter: step-time EMA, tokens/sec, loss/grad-norm
                      tracking for the train/serve drivers (--stats).
  repro.obs.telemetry -- streaming schema-versioned JSONL event log (step
                      time, sampled per-bucket reduce times, tok/s, alarms)
                      cheap enough to leave on for a whole run.
  repro.obs.detect -- HealthMonitor: online measured-vs-modeled residual
                      tracking with EWMA/robust-z detectors classifying
                      sustained drift into typed alarms (straggler /
                      link_degraded / step_time_drift), each carrying a
                      Topology.degrade-ready factor estimate and a
                      "would re-route K buckets" reaction hook.

Layering: trace.py and telemetry.py depend on nothing in repro (core modules
may emit their schemas without a cycle); stats.py and detect.py sit ABOVE
repro.core (core reaches them only through lazy imports).
"""
