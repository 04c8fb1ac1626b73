"""A cell, a configuration, a traffic mix and a metric are found by name:
files dropped into a copy of the layout are discovered and run, with no
edit to any file that was there."""
import json
import shutil

import jax

from chipbench import harness


def test_new_files_are_found_and_run(tmp_path):
    base = tmp_path / "chipbench"
    shutil.copytree(harness.HERE, base,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    before = harness.Layout(base, tmp_path).discover()
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())

    # a new deployment, a new traffic mix and a new per-layer metric
    (base / "configs" / "tiny-cfg.json").write_text(json.dumps(
        {"s": 8, "update": {"params": 16, "bytes": 64, "scale": 0.1,
                            "leaves": [["w", [4, 4], "float32"]]}}))
    (base / "traffic" / "round.k2.tiny.json").write_text(json.dumps(
        {"entry": "fednc_round", "clients": 2, "kernel": "auto",
         "extra_tuples": 1, "p_erase": 0.1}))
    (base / "metrics" / "attempts_per_round.py").write_text(
        'LAYER = "entry"\nUNIT = "attempts"\nMOVES = "round_s"\n\n\n'
        'def read(run):\n    return run.obs["attempts"] / run.obs["rounds"]\n')
    bench["workloads"].append({"name": "round.tiny-cfg.k2",
                               "config": "tiny-cfg",
                               "traffic": "round.k2.tiny", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "round_s":
            m["workloads"].append("round.tiny-cfg.k2")
    bench["per_layer"].append({"name": "attempts_per_round",
                               "unit": "attempts", "better": "lower",
                               "source": "host_clock", "layer": "entry",
                               "moves": "round_s",
                               "workloads": ["round.tiny-cfg.k2"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    layout = harness.Layout(base, tmp_path)
    after = layout.discover()
    new = {k: sorted(set(after[k]) - set(before[k])) for k in after}
    assert new == {"configs": ["tiny-cfg"], "traffic": ["round.k2.tiny"],
                   "paths": [], "metrics": ["attempts_per_round"]}

    for trace, want in ((False, {"round_s", "setup_s"}),
                        (True, {"attempts_per_round"})):
        assert {m["name"] for m in harness.cell_metrics(
            layout.benchmark(), "round.tiny-cfg.k2", trace)} >= want
        r = harness.run_cell("round.tiny-cfg.k2", 11, 0.2, trace,
                             layout=layout, devices=jax.devices(),
                             log=lambda rec: None)
        assert r["correct"]
        assert want <= set(r["metrics"])
