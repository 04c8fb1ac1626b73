"""CPU rehearsal of each path module at tiny sizes: sound runs come out
correct; the control (the plain reference one precision lower, in the
program's place) and each fault the cell can have come out not correct.
"""
import contextlib
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from chipbench import harness

TINY = {"s": 8, "update": {"params": 79, "bytes": 188, "scale": 0.02,
                           "leaves": [["a", [64], "bfloat16"],
                                      ["b", [3, 5], "float32"]]}}
LAYOUT = harness.Layout()
ROUND_CELLS = {"round.xsilo-xlstm125m.k8": "round.k8.erasure5",
               "round.xdevice-cnn.k128": "round.k128.ideal-seeded"}
SERVE = "serve.xdevice-cnn.gen16"


def _round_traffic(cell):
    return dict(LAYOUT.traffic(ROUND_CELLS[cell]), clients=3)


def _serve_traffic():
    return dict(LAYOUT.traffic("serve.gen16.open"), k=3, l=64,
                extra_packets=2, slots=4, rate_gen_per_s=20)


def _run(cell, traffic, seed=2**31 + 5, trace=False, control=False,
         seconds=0.3):
    return harness.run_cell(cell, seed, seconds, trace,
                            devices=jax.devices(), config=TINY,
                            traffic=traffic, control=control, drain_s=3.0,
                            log=lambda rec: None)


@pytest.mark.parametrize("cell", sorted(ROUND_CELLS))
@pytest.mark.parametrize("trace", [False, True])
def test_round_cell_is_correct(cell, trace):
    r = _run(cell, _round_traffic(cell), trace=trace)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] >= 1
    want = {m["name"] for m in harness.cell_metrics(
        LAYOUT.benchmark(), cell, trace)}
    # the CPU runs the jnp kernels, whose names hold no "gf_matmul"
    assert set(r["metrics"]) == want - {
        "gf_kernel_ms.round", "gf_matmul_roofline", "peak_hbm_gb"}
    assert list(r)[-1] == "checks"


@pytest.mark.parametrize("trace", [False, True])
def test_serve_cell_is_correct(trace):
    r = _run(SERVE, _serve_traffic(), trace=trace)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] >= 3
    want = {m["name"] for m in harness.cell_metrics(
        LAYOUT.benchmark(), SERVE, trace)}
    assert set(r["metrics"]) == want


@pytest.mark.parametrize("cell", sorted(ROUND_CELLS))
def test_round_control_fails(cell):
    r = _run(cell, _round_traffic(cell), control=True)
    checks = {c["name"]: c["value"] for c in r["checks"]}
    assert not r["correct"]
    assert checks["rounds_wrong"] == checks["rounds_checked"] >= 1


def test_serve_control_fails():
    r = _run(SERVE, _serve_traffic(), control=True)
    assert not r["correct"]


# -- faults planted under the timed path ------------------------------------

def _state_unchanged(orig):
    def fault(state, clients, key_seed, chan_seed):
        return SimpleNamespace(global_params=clients[0], decoded=True)
    return fault


def _half_batch(orig):
    def fault(state, clients, key_seed, chan_seed):
        half = clients[: len(clients) // 2 or 1]
        sub = SimpleNamespace(**{**vars(state),
                                 "weights": [1.0 / len(half)] * len(half)})
        return orig(sub, half, key_seed, chan_seed)
    return fault


def _answer_altered(orig):
    def fault(state, clients, key_seed, chan_seed):
        res = orig(state, clients, key_seed, chan_seed)
        leaves = list(res.global_params)
        x = leaves[-1]
        leaves[-1] = x.reshape(-1).at[0].add(1).reshape(x.shape)
        return SimpleNamespace(global_params=leaves, decoded=res.decoded)
    return fault


@pytest.mark.parametrize("cell", sorted(ROUND_CELLS))
@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _answer_altered])
def test_round_faults_are_caught(cell, fault, monkeypatch):
    mod = LAYOUT.path("fednc_round")
    monkeypatch.setattr(mod, "_program_round", fault(mod._program_round))
    r = _run(cell, _round_traffic(cell))
    assert not r["correct"]
    assert {c["name"]: c["value"] for c in r["checks"]}["rounds_wrong"] >= 1


@contextlib.contextmanager
def _serve_fault(kind, monkeypatch):
    from repro.engine import DecoderBank
    from repro.serve import DecodeServer
    if kind == "answer_altered":
        orig = DecodeServer.result

        def result(self, job):
            out = np.array(orig(self, job))
            out[0, 0] ^= 1
            return out
        monkeypatch.setattr(DecodeServer, "result", result)
    elif kind == "state_unchanged":
        def ingest(self, rows=None, seeds=None, use_seed=None, valid=None,
                   C=None, *, batched=True):
            return np.zeros(np.shape(valid), np.int32)
        monkeypatch.setattr(DecoderBank, "ingest", ingest)
    elif kind == "half_batch":
        orig = DecodeServer.offer
        seen = [0]

        def offer(self, job, payload, *, seed=0, row=None):
            seen[0] += 1
            if seen[0] % 2:
                return True         # every other packet is left out
            return orig(self, job, payload, seed=seed, row=row)
        monkeypatch.setattr(DecodeServer, "offer", offer)
    yield


@pytest.mark.parametrize("kind", ["answer_altered", "state_unchanged",
                                  "half_batch"])
def test_serve_faults_are_caught(kind, monkeypatch):
    with _serve_fault(kind, monkeypatch):
        mod = LAYOUT.path("decode_server")
        # the warm-up would never end with a broken server: skip it
        monkeypatch.setattr(mod, "_drive", _drive_with_cap(mod._drive))
        r = _run(SERVE, _serve_traffic())
    assert not r["correct"]


def _drive_with_cap(orig):
    def drive(srv, tf, seconds, drain_s, annotate=None):
        return orig(srv, tf, seconds, min(drain_s, 3.0), annotate)
    return drive
