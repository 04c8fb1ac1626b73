"""The program-span metrics on the CPU at tiny sizes: each reads a finite
number from the program's per-call records, and only the window's
records (set-up's warm-up rounds and ticks are in the ring too)."""
import math

import jax
import numpy as np
import pytest

from chipbench import harness
from chipbench.tests.test_paths import (ROUND_CELLS, SERVE, TINY,
                                        _round_traffic, _serve_traffic)
from repro import obs

ROUND_SPANS = {"packetize_host_ms.round": ("fednc.packetize",),
               "stream_host_ms.round": ("engine.encode", "engine.decode"),
               "aggregate_host_ms.round": ("fednc.aggregate",),
               "host_wait_ms.round": ("fednc.sync",)}


def _run(cell, traffic):
    """A traced run; returns its result, the window's description, and
    the records the run left, oldest first."""
    logs = []
    t0 = obs.clock()
    r = harness.run_cell(cell, 2**31 + 7, 0.3, True, devices=jax.devices(),
                         config=TINY, traffic=traffic, drain_s=3.0,
                         log=logs.append)
    return r, logs[0], [rec for rec in obs.records() if rec.t0 >= t0]


def _value(r, name):
    v = r["metrics"][name]["value"]
    assert math.isfinite(v) and v > 0, (name, v)
    return v


@pytest.mark.parametrize("cell", sorted(ROUND_CELLS))
def test_round_phase_metrics_read_the_window(cell):
    r, win, recs = _run(cell, _round_traffic(cell))
    assert r["correct"]
    recs = [rec for rec in recs if rec.name == "fednc.round"]
    attempts, rounds = win["attempts"], win["rounds"]
    assert len(recs) > attempts >= rounds >= 1     # warm-up rounds first
    window = recs[-attempts:]
    for name, spans in ROUND_SPANS.items():
        want = sum(rec.spans.get(s, 0) for rec in window
                   for s in spans) / 1e6 / rounds
        assert _value(r, name) == pytest.approx(want)


def test_serve_phase_metrics_read_the_window():
    r, win, recs = _run(SERVE, _serve_traffic())
    assert r["correct"]
    recs = [rec for rec in recs if rec.name == "serve.tick"]
    ticks = win["ticks"]
    assert len(recs) > ticks >= 1                  # warm-up ticks first
    window = recs[-ticks:]
    valid = sum(rec.counts["serve.packets_ingested"] for rec in window)
    capacity = sum(rec.counts["serve.block_capacity"] for rec in window)
    waits = [w for rec in window for w in rec.samples["serve.queue_wait_s"]]
    host = sum(rec.spans["serve.schedule"] + rec.spans["serve.complete"]
               for rec in window) / 1e6 / ticks
    assert _value(r, "tick_fill_pct.serve") \
        == pytest.approx(100 * valid / capacity)
    assert _value(r, "tick_fill_pct.serve") <= 100
    assert _value(r, "queue_wait_p95_ms.serve") == pytest.approx(
        1e3 * np.quantile(waits, 0.95, method="higher"))
    assert _value(r, "tick_host_ms.serve") == pytest.approx(host)
