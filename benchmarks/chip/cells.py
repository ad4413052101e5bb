"""Where the harness finds a cell's pieces: by the names in BENCHMARK.json.

    configs/<config>.json    the configuration as it is run
    configs/<config>.py      its plain reference and FLOPs per token
    traffic/<traffic>.json   the job: batch, sequence, mesh, comm
    workloads/<cell>.json    the cell's comparison limits and trace length
    metrics/<metric>.py      one reader per per-layer metric
    peaks.json               the chips' peaks, keyed by device_kind

A later cell, configuration or metric is a new file of these kinds and an
entry in BENCHMARK.json; nothing here names one.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]


class SpecError(Exception):
    """A name in BENCHMARK.json without its file, or a file out of shape."""


def load_module(path: pathlib.Path, name: str):
    if not path.is_file():
        raise SpecError(f"missing {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: pathlib.Path) -> dict:
    if not path.is_file():
        raise SpecError(f"missing {path}")
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    cfg: dict             # configs/<config>.json
    ref: object           # configs/<config>.py
    traffic: dict         # traffic/<traffic>.json
    own: dict             # workloads/<cell>.json
    end_to_end: list      # BENCHMARK.json's end-to-end entries: every cell
    per_layer: list       # (entry, reader module) of the metrics listing it

    @property
    def tokens_per_step(self) -> int:
        return self.traffic["batch"] * self.traffic["seq"]


class Bench:
    """BENCHMARK.json under the checkout `root`, and the harness's files at
    the same place under it as in this checkout."""

    def __init__(self, root: pathlib.Path = ROOT):
        self.root, self.here = root, root / HERE.relative_to(ROOT)
        self.spec = load_json(root / "BENCHMARK.json")
        self.peaks = load_json(self.here / "peaks.json")["devices"]
        for m in self.spec["per_layer"]:
            if "workloads" not in m:
                raise SpecError(f"per-layer metric {m['name']!r} lists no "
                                f"workloads")

    def peak(self, device_kind: str) -> dict:
        if device_kind not in self.peaks:
            raise SpecError(f"no peaks for device_kind {device_kind!r} in "
                            f"peaks.json (known: {sorted(self.peaks)})")
        return self.peaks[device_kind]

    def cell(self, name: str) -> Cell:
        entry = next((w for w in self.spec["workloads"] if w["name"] == name),
                     None)
        if entry is None:
            raise SpecError(f"no workload {name!r} in BENCHMARK.json")
        cfg_entry = next((c for c in self.spec["configs"]
                          if c["name"] == entry["config"]), None)
        if cfg_entry is None:
            raise SpecError(f"no configuration {entry['config']!r} in "
                            f"BENCHMARK.json")
        cfg = load_json(self.root / cfg_entry["file"])
        ref = load_module(self.here / "configs" / f"{entry['config']}.py",
                          f"ref_{entry['config']}")
        per_layer = [(m, load_module(self.here / "metrics" / f"{m['name']}.py",
                                     f"metric_{m['name']}"))
                     for m in self.spec["per_layer"]
                     if name in m["workloads"]]
        return Cell(name=name, chips=entry["chips"], cfg=cfg, ref=ref,
                    traffic=load_json(self.here / "traffic"
                                      / f"{entry['traffic']}.json"),
                    own=load_json(self.here / "workloads" / f"{name}.json"),
                    end_to_end=self.spec["end_to_end"], per_layer=per_layer)
