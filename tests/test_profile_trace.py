"""The training step's one trace: the profiler's, on one clock.

  * `data/pipeline.py` makes each batch inside a `data/batch` host span,
    which lands in a `jax.profiler` trace beside the device ops;
  * `launch/train.py --trace DIR` writes that trace (an xplane file): one
    `train` step span per step, the batches' `data/batch` spans, and the
    ops of the compiled step.
"""

import glob
import os

import jax
from jax.profiler import ProfileData

from repro.configs import registry
from repro.data import pipeline
from repro.launch import train


def _xplane(trace_dir: str) -> ProfileData:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    assert len(files) == 1, files
    return ProfileData.from_file(files[0])


def _events(data: ProfileData, plane_prefix: str):
    for plane in data.planes:
        if plane.name.startswith(plane_prefix):
            for line in plane.lines:
                yield from line.events


def test_data_batch_span_reaches_the_profiler_trace(tmp_path):
    dcfg = pipeline.DataConfig(vocab=64, seq_len=16, global_batch=4)
    jax.profiler.start_trace(str(tmp_path))
    try:
        for s in range(3):
            pipeline.batch_at(dcfg, s)
    finally:
        jax.profiler.stop_trace()
    spans = [e for e in _events(_xplane(str(tmp_path)), "/host:")
             if e.name == "data/batch"]
    assert len(spans) == 3
    assert all(e.duration_ns > 0 for e in spans)


def test_train_trace_is_a_profiler_trace(tmp_path):
    args = train.build_parser().parse_args(
        ["--comm", "mlsl", "--hier", "--wire", "int8", "--error-feedback",
         "--steps", "3", "--batch", "16", "--seq", "32", "--log-every", "1",
         "--trace", str(tmp_path)])
    res = train.run(registry.get_smoke_config("yi-6b"), args)
    assert len(res.history) == 3
    host = list(_events(_xplane(str(tmp_path)), "/host:"))
    steps = sorted(dict(e.stats)["step_num"] for e in host
                   if e.name == "train")
    assert steps == [0, 1, 2]
    # the first batch is made before the compile, outside the trace
    assert sum(e.name == "data/batch" for e in host) == 2
    modules = {dict(e.stats).get("hlo_module") for e in host}
    assert any(m and "train_step" in m for m in modules), modules
    # no second writer beside the profiler
    assert not glob.glob(os.path.join(str(tmp_path), "*.json"))
