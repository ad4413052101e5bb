"""BENCHMARK.json and the files the harness finds by its names."""

import json
import re

import pytest

import cells
import compare
import program

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return cells.Bench()


def test_every_cell_resolves(bench):
    for w in bench.spec["workloads"]:
        cell = bench.cell(w["name"])
        assert cell.chips in (1, 4)
        assert set(cell.own["limits"]) <= set(compare.NUMBERS)
        assert cell.own["limits"], f"{w['name']} compares nothing"
        assert cell.end_to_end and cell.per_layer
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s",
                                                        "tokens_per_s"}
        for _, reader in cell.per_layer:
            assert callable(reader.read)


def test_configuration_files_match_the_program(bench):
    for c in bench.spec["configs"]:
        cfg = json.loads((cells.ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert set(c["reduced"]) == set(cfg["reduced"])
        ref = cells.load_module(cells.HERE / "configs" / f"{c['name']}.py",
                                f"ref_{c['name']}")
        program.model_config(cfg)             # raises on any difference
        assert ref.flops_per_token(cfg, 2048) > 6 * 1e8


def test_benchmark_json_keeps_to_its_limits(bench):
    spec = bench.spec
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= spec["run_seconds"] <= 51
    check_s = (2 + 14 * 24) * (spec["run_seconds"] + 60) + 24 * 180 + 1200
    assert check_s <= 43200
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in spec[k]]
    assert all(NAME.match(n) for n in names)
    metric_names = [m["name"] for m in spec["end_to_end"] + spec["per_layer"]]
    assert len(set(metric_names)) == len(metric_names)
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in spec["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    e2e = {m["name"] for m in spec["end_to_end"]}
    layers = {}
    for m in spec["per_layer"]:
        assert m["moves"] in e2e
        layers.setdefault(m["layer"], set()).add(m["name"].split(".")[0])
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"
    for w in spec["workloads"]:
        assert len(w["why"]) <= 200
        assert (cells.HERE / "traffic" / f"{w['traffic']}.json").is_file()
    assert sum(w["chips"] == 4 for w in spec["workloads"]) <= max(
        1, len(spec["workloads"]) // 2)
    assert len(json.dumps(spec)) < 64 * 1024


def test_peaks_are_keyed_by_device_kind(bench):
    assert bench.peak("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(cells.SpecError, match="no peaks"):
        bench.peak("TPU v9 imaginary")


def test_per_layer_metric_without_workloads_is_an_error(tmp_path):
    chip = tmp_path / "benchmarks" / "chip"
    chip.mkdir(parents=True)
    (chip / "peaks.json").write_text((cells.HERE / "peaks.json").read_text())
    spec = json.loads((cells.ROOT / "BENCHMARK.json").read_text())
    del spec["per_layer"][0]["workloads"]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    with pytest.raises(cells.SpecError, match="lists no workloads"):
        cells.Bench(tmp_path)


def test_missing_file_is_an_error(tmp_path):
    (tmp_path / "benchmarks" / "chip").mkdir(parents=True)
    with pytest.raises(cells.SpecError, match="BENCHMARK.json"):
        cells.Bench(tmp_path)
