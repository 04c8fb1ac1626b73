"""CPU rehearsal of the expert-selective round cell at a tiny size: sound
runs come out correct; both controls and each planted fault come out not
correct."""
from types import SimpleNamespace

import jax
import pytest

from chipbench import harness

CELL = "round.xsilo-deepseek-v2-lite-ep8.k8-experts"
LAYOUT = harness.Layout()
TINY = {"s": 8,
        "update": {"params": 0, "bytes": 0, "scale": 0.02,
                   "leaves": [["a", [64], "bfloat16"],
                              ["b", [3, 5], "float32"],
                              ["moe.w_down", [2, 4, 6, 8], "bfloat16"],
                              ["moe.w_gate", [2, 4, 8, 6], "bfloat16"]]},
        "experts": {"leaves": ["moe.w_down", "moe.w_gate"], "grid": [2, 4],
                    "held": [0, 1, 2, 3], "of": 16}}
# selection_seed 8 gives the 8 blocks 1, 0, 4, 4, 0, 1, 3, 1 holders:
# blocks nobody, everybody and a single silo trained
TRAFFIC = dict(LAYOUT.traffic("round.k8.experts16of64-erasure5"),
               clients=4, weights=[1, 2, 3, 4], trained_per_layer=4,
               selection_seed=8, p_erase=0.1)


def _run(trace=False, control=False, traffic=TRAFFIC):
    return harness.run_cell(CELL, 2**31 + 9, 0.3, trace,
                            devices=jax.devices(), config=TINY,
                            traffic=traffic, control=control,
                            log=lambda rec: None)


def test_the_selection_covers_every_kind_of_block():
    mod = LAYOUT.path("fednc_round_experts")
    sel = mod.trained(16, 4, 2, 4, 8)
    holders = [sum(j in sel[l][k] for k in range(4))
               for l in range(2) for j in range(4)]
    assert holders == [1, 0, 4, 4, 0, 1, 3, 1]


@pytest.mark.parametrize("kernel", ["auto", "auto_seeded"])
@pytest.mark.parametrize("trace", [False, True])
def test_experts_cell_is_correct(trace, kernel):
    r = _run(trace, traffic=dict(TRAFFIC, kernel=kernel))
    assert r["correct"] and r["failed"] == 0 and r["attempted"] >= 1
    want = {m["name"] for m in harness.cell_metrics(
        LAYOUT.benchmark(), CELL, trace)}
    # the CPU runs the jnp kernels, whose names hold no "gf_matmul"
    assert set(r["metrics"]) == want - {
        "gf_kernel_ms.round", "gf_matmul_roofline.gens", "peak_hbm_gb"}


def test_both_controls_fail():
    r = _run(control=True)
    checks = {c["name"]: c["value"] for c in r["checks"]}
    assert not r["correct"]
    assert checks["rounds_wrong"] == checks["rounds_checked"] >= 2
    assert checks["rounds_wrong.bf16_mean"] >= 1
    assert checks["rounds_wrong.absent_as_zero"] >= 1


def _state_unchanged(orig):
    def fault(state, *seeds):
        return SimpleNamespace(global_params=state.prev, decoded=True)
    return fault


def _a_holder_dropped(orig):
    def fault(state, *seeds):
        from repro.core import packets as pkt
        clients = [dict(c) for c in state.clients]
        name = next(n for n, x in clients[0].items() if pkt.is_blocks(x))
        k = max(range(len(clients)), key=lambda k: len(state.held[k]))
        for n, x in clients[k].items():
            if pkt.is_blocks(x) and n.startswith(name.rsplit(".", 1)[0]):
                clients[k][n] = pkt.Blocks(x.rows[1:], x.ids[1:], x.grid)
        return orig(SimpleNamespace(**{**vars(state), "clients": clients}),
                    *seeds)
    return fault


def _answer_altered(orig):
    def fault(state, *seeds):
        res = orig(state, *seeds)
        out = dict(res.global_params)
        x = out["moe.w_gate"]
        out["moe.w_gate"] = x.at[0, 0, 0, 0].add(1)
        return SimpleNamespace(global_params=out, decoded=res.decoded)
    return fault


@pytest.mark.parametrize("fault", [_state_unchanged, _a_holder_dropped,
                                   _answer_altered])
def test_faults_are_caught(fault, monkeypatch):
    mod = LAYOUT.path("fednc_round_experts")
    monkeypatch.setattr(mod, "_program_round", fault(mod._program_round))
    r = _run()
    assert not r["correct"]
    assert {c["name"]: c["value"] for c in r["checks"]}["rounds_wrong"] >= 1
