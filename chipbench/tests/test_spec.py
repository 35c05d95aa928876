"""The benchmark is driven by data: a configuration, a traffic mix, a loop
kind or a metric is found by its name, and ``BENCHMARK.json`` names only
pieces that exist and keeps its documented shape."""
from __future__ import annotations

import json
import re

import pytest

from chipbench import spec

BENCH = spec.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_new_pieces_dropped_into_a_directory_are_found_by_name(tmp_path):
    (tmp_path / "configs").mkdir()
    (tmp_path / "traffic").mkdir()
    (tmp_path / "loops").mkdir()
    (tmp_path / "metrics").mkdir()
    cfg = spec.load_config("vpc8-r1k")
    cfg["deployment"]["tenants"] = 3
    (tmp_path / "configs" / "new-cfg.json").write_text(json.dumps(cfg))
    (tmp_path / "traffic" / "new-mix.json").write_text(
        json.dumps({"loop": "new_loop", "batch_pkts": 7}))
    (tmp_path / "loops" / "new_loop.py").write_text(
        "class Loop:\n    KIND = 'new'\n")
    (tmp_path / "metrics" / "new_metric.py").write_text(
        "def read(r):\n    return 41.5\n")
    (tmp_path / "peaks.json").write_text(json.dumps(
        {"source": "s", "devices": {"New Chip": {"hbm_bytes_per_s": 1.0}}}))
    assert spec.load_config("new-cfg", tmp_path)["deployment"][
        "tenants"] == 3
    mix = spec.load_traffic("new-mix", tmp_path)
    assert spec.load_loop(mix["loop"], tmp_path).Loop.KIND == "new"
    assert spec.load_metric("new_metric", tmp_path).read(None) == 41.5
    assert spec.load_peaks("New Chip", tmp_path)["hbm_bytes_per_s"] == 1.0


def test_a_missing_piece_is_an_error(tmp_path):
    with pytest.raises(FileNotFoundError):
        spec.load_config("nope", tmp_path)
    with pytest.raises(FileNotFoundError):
        spec.load_metric("nope", tmp_path)


def test_an_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        spec.load_peaks("TPU v0 imaginary")
    assert spec.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_metrics_for_follows_workloads_lists():
    bench = {"per_layer": [{"name": "a"}, {"name": "b", "workloads": ["x"]}]}
    assert [m["name"] for m in spec.metrics_for(bench, "x", "per_layer")] \
        == ["a", "b"]
    assert [m["name"] for m in spec.metrics_for(bench, "y", "per_layer")] \
        == ["a"]


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda c: c["name"])
def test_every_cell_names_pieces_that_exist(cell):
    cfg = spec.load_config(cell["config"])
    traffic = spec.load_traffic(cell["traffic"])
    assert spec.load_loop(traffic["loop"]).Loop
    assert cfg["chips"] == cell["chips"]
    assert cell["chips"] in (1, 4)
    e2e = {m["name"] for m in spec.metrics_for(BENCH, cell["name"],
                                               "end_to_end")}
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = spec.metrics_for(BENCH, cell["name"], "per_layer")
    assert layer
    for m in layer:
        assert m["moves"] in e2e, (m["name"], m["moves"])


@pytest.mark.parametrize("kind", ["end_to_end", "per_layer"])
def test_every_metric_has_a_reader_and_a_valid_shape(kind):
    cells = {c["name"] for c in BENCH["workloads"]}
    for m in BENCH[kind]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert callable(spec.load_metric(m["name"]).read)
        assert set(m.get("workloads", cells)) <= cells
        if kind == "end_to_end":
            assert 0.01 <= m["bound"] <= 0.25
            assert m["source"] in ("host_clock", "device_trace")


def test_configs_are_their_files_and_used():
    used = {c["config"] for c in BENCH["workloads"]}
    for c in BENCH["configs"]:
        assert c["name"] in used
        assert c["file"] == f"chipbench/configs/{c['name']}.json"
        cfg = spec.load_config(c["name"])
        assert cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"]
        dep = cfg["deployment"]
        assert sum(dep["chains"].values()) == dep["tenants"]


def test_benchmark_json_keeps_its_documented_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    keys = {"configs": {"name", "source", "file", "reduced", "why"},
            "workloads": {"name", "config", "traffic", "chips", "why"},
            "end_to_end": {"name", "unit", "better", "bound", "source",
                           "workloads"},
            "per_layer": {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}}
    for kind, allowed in keys.items():
        names = [e["name"] for e in BENCH[kind]]
        assert len(names) == len(set(names))
        for e in BENCH[kind]:
            assert set(e) <= allowed, (kind, e["name"])
            assert NAME.match(e["name"])
            for k in ("why", "source", "layer"):
                if k in e and kind != "end_to_end":
                    assert 1 <= len(e[k]) <= 200 and "\n" not in e[k]
    pairs = [(c["config"], c["traffic"]) for c in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(c["chips"] == 4 for c in BENCH["workloads"])
    assert four <= max(1, len(pairs) // 2)
