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


def cut_problems(entry: dict, cfg: dict) -> list:
    """What the configuration file leaves out of the cut its entry lists:
    an empty ``reduced`` needs nothing; a cut needs the same ``reduced``
    in the file, the ``published`` value of every key cut, a
    ``deployment`` and the entry's ``source``."""
    cut = entry["reduced"]
    if not cut:
        return []
    problems = []
    if cfg.get("reduced") != cut:
        problems.append("the file's reduced differs from the entry's")
    published = cfg.get("published") or {}
    problems += [f"no published value of {k}" for k in cut
                 if k not in published]
    if not cfg.get("deployment"):
        problems.append("no deployment")
    if cfg.get("source") != entry["source"]:
        problems.append("the file's source differs from the entry's")
    return problems


def chip_problems(workloads: list) -> list:
    """Cells on another number of chips than 1 or 4, and 4-chip cells
    beyond half of the cells (one always may)."""
    problems = [f"{w['name']} asks for {w['chips']} chips"
                for w in workloads if w["chips"] not in (1, 4)]
    four = sum(w["chips"] == 4 for w in workloads)
    cap = max(1, len(workloads) // 2)
    if four > cap:
        problems.append(f"{four} four-chip cells, at most {cap}")
    return problems


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
        assert not cut_problems(c, LAYOUT.config(c["name"]))


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_every_cell_reports_setup_another_e2e_and_a_per_layer_metric(w):
    tr = LAYOUT.traffic(w["traffic"])
    assert tr["entry"] in LAYOUT.discover()["paths"]
    assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    e2e = {m["name"] for m in harness.cell_metrics(BENCH, w["name"], False)}
    assert "setup_s" in e2e and len(e2e) >= 2
    per = harness.cell_metrics(BENCH, w["name"], True)
    assert per and all(m["moves"] in e2e for m in per)


def test_four_chip_cells_within_the_cap():
    assert not chip_problems(BENCH["workloads"])


_CUT = {"name": "cut", "source": "https://example.org/cut",
        "file": "chipbench/configs/cut.json",
        "reduced": ["num_hidden_layers", "n_routed_experts"], "why": "w"}
_CUT_FILE = {"source": "https://example.org/cut",
             "deployment": "8 chips share each layer",
             "reduced": ["num_hidden_layers", "n_routed_experts"],
             "published": {"num_hidden_layers": 27, "n_routed_experts": 64}}


@pytest.mark.parametrize("entry,cfg", [(_CUT, _CUT_FILE),
                                       (dict(_CUT, reduced=[]), {})],
                         ids=["cut_written_in_full", "nothing_cut"])
def test_a_documented_cut_passes(entry, cfg):
    assert cut_problems(entry, cfg) == []


@pytest.mark.parametrize("edit", [
    {"published": {"num_hidden_layers": 27}},
    {"published": None},
    {"reduced": ["num_hidden_layers"]},
    {"reduced": None},
    {"deployment": ""},
    {"source": "https://example.org/other"},
], ids=["a_key_without_published_value", "no_published",
        "file_reduced_differs", "file_without_reduced", "no_deployment",
        "source_differs"])
def test_an_undocumented_cut_is_rejected(edit):
    cfg = {k: v for k, v in {**_CUT_FILE, **edit}.items() if v is not None}
    assert cut_problems(_CUT, cfg)


def _cells(*chips):
    return [{"name": f"c{i}", "chips": n} for i, n in enumerate(chips)]


@pytest.mark.parametrize("chips", [(4,), (1, 4), (1, 1, 4, 4), (1, 1, 1, 4)],
                         ids=str)
def test_four_chip_cells_up_to_the_cap_pass(chips):
    assert chip_problems(_cells(*chips)) == []


@pytest.mark.parametrize("chips", [(2,), (1, 8), (4, 4), (1, 1, 4, 4, 4),
                                   (1, 4, 4)], ids=str)
def test_other_chip_counts_and_cells_over_the_cap_are_rejected(chips):
    assert chip_problems(_cells(*chips))


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
