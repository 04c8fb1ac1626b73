"""BENCHMARK.json against the files it names, and each metric reader's
declarations against its entry."""
import json
import re

import pytest

from chipbench import harness

LAYOUT = harness.Layout()
BENCH = LAYOUT.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def test_top_level_keys_and_sizes():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "chipbench/run.py"]
    assert BENCH["paths"] == ["chipbench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_names_are_unique_and_well_formed():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BENCH[group]]
        assert all(NAME.match(n) for n in names), names
    all_metrics = [m["name"] for m in METRICS]
    assert len(set(all_metrics)) == len(all_metrics)


def test_every_config_file_exists_and_is_used():
    used = {w["config"] for w in BENCH["workloads"]}
    for c in BENCH["configs"]:
        assert c["file"] == f"chipbench/configs/{c['name']}.json"
        assert LAYOUT.config(c["name"])["update"]["leaves"]
        assert c["name"] in used
        assert c["reduced"] == []


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_every_cell_reports_setup_another_e2e_and_a_per_layer_metric(w):
    tr = LAYOUT.traffic(w["traffic"])
    assert tr["entry"] in LAYOUT.discover()["paths"]
    assert w["chips"] == 1 and len(w["why"]) <= 200
    e2e = {m["name"] for m in harness.cell_metrics(BENCH, w["name"], False)}
    assert "setup_s" in e2e and len(e2e) >= 2
    per = harness.cell_metrics(BENCH, w["name"], True)
    assert per and all(m["moves"] in e2e for m in per)


@pytest.mark.parametrize("m", METRICS, ids=lambda m: m["name"])
def test_each_reader_matches_its_entry(m):
    reader = LAYOUT.metric(m["name"])
    assert reader.UNIT == m["unit"]
    assert reader.MOVES == m.get("moves")
    assert reader.LAYER == m.get("layer")
    if "bound" in m:
        assert 0.01 <= m["bound"] <= 0.25
    assert m["source"] in ("device_trace", "program_span",
                           "program_counter", "host_clock")


def test_peaks_table_names_its_source():
    doc = json.loads((harness.HERE / "peaks.json").read_text())
    assert "TPU v5e" in doc["source"]
    assert doc["devices"]["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
