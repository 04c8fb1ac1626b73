"""repro.obs: trace round-trip, metric merge laws, no-op neutrality.

The observability layer must never change what it observes: the
NULL_TRACER path has to be bit-exact with the traced path and run
the same programs and host syncs, snapshots must merge associatively
(grid workers reduce in arbitrary order), the serialized artifacts
must stay valid Chrome trace-event JSON (the contract
scripts/check_bench.py re-checks standalone), and every entry call
leaves one per-call record whose phases the chip benchmark reads.
"""
import glob
import json
import os
import random
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core.channel import ErasureChannel
from repro.core.fednc import FedNCConfig, fednc_round
from repro.core.gf import get_field
from repro.engine import CodingEngine, EngineConfig
from repro.serve import poisson_multitenant_trace, serve_trace

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                                    # pragma: no cover
    HAVE_HYPOTHESIS = False


@pytest.fixture(autouse=True)
def _null_tracer():
    """Every test starts and ends with tracing disabled."""
    obs.set_tracer(obs.NULL_TRACER)
    yield
    obs.set_tracer(obs.NULL_TRACER)


# ---------------------------------------------------------------------------
# Trace document round-trip
# ---------------------------------------------------------------------------


def test_trace_round_trips_as_valid_chrome_json(tmp_path):
    tr = obs.Tracer(process_name="test")
    with tr.span("outer", cat="t", k=3) as sp:
        with tr.span("inner", cat="t"):
            pass
        sp.set(done=True)
    tr.instant("mark", cat="t", x=1)
    tr.counter("depth", 7)
    path = tr.save(tmp_path / "TRACE_t.json")

    doc = json.loads(path.read_text())
    assert doc["otherData"]["schema"] == obs.TRACE_SCHEMA
    events = obs.load_trace(path)
    assert obs.validate_trace(events) == []
    by_ph = {}
    for ev in events:
        by_ph.setdefault(ev["ph"], []).append(ev)
    assert {e["name"] for e in by_ph["X"]} == {"outer", "inner"}
    outer = next(e for e in by_ph["X"] if e["name"] == "outer")
    inner = next(e for e in by_ph["X"] if e["name"] == "inner")
    # the span nesting holds on the timeline, and set() args landed
    assert outer["ts"] <= inner["ts"]
    assert outer["ts"] + outer["dur"] >= inner["ts"] + inner["dur"]
    assert outer["args"] == {"k": 3, "done": True}
    assert by_ph["i"][0]["s"] == "t"
    assert by_ph["C"][0]["args"] == {"depth": 7.0}
    assert by_ph["M"][0]["args"]["name"] == "test"


def test_validate_trace_rejects_malformed_events():
    assert obs.validate_trace([{"ph": "X"}])      # no name
    assert obs.validate_trace(
        [{"name": "a", "ph": "X", "pid": 1, "tid": 0, "ts": 0.0}])
    assert obs.validate_trace(
        [{"name": "q", "ph": "C", "ts": 0.0, "pid": 1, "tid": 0,
          "args": {"q": "high"}}])
    # metadata events are exempt from ts/pid/tid
    assert obs.validate_trace(
        [{"name": "process_name", "ph": "M", "args": {"name": "w"}}]) \
        == []


def test_merge_keeps_pid_lanes_and_orders_by_time():
    a = [{"name": "s", "ph": "X", "ts": 10.0, "dur": 1.0, "pid": 1,
          "tid": 0}]
    b = [{"name": "process_name", "ph": "M", "pid": 2, "tid": 0,
          "args": {"name": "w"}},
         {"name": "s", "ph": "X", "ts": 5.0, "dur": 1.0, "pid": 2,
          "tid": 0}]
    merged = obs.merge_events(a, b)
    assert [e["ph"] for e in merged] == ["M", "X", "X"]
    assert [e.get("pid") for e in merged] == [2, 2, 1]
    assert obs.summarize(merged)["processes"] == 2


def test_stage_totals_excludes_envelopes():
    evs = [{"name": "outer", "ph": "X", "ts": 0, "dur": 5e6, "pid": 1,
            "tid": 0},
           {"name": "leaf", "ph": "X", "ts": 0, "dur": 2e6, "pid": 1,
            "tid": 0},
           {"name": "leaf", "ph": "X", "ts": 2e6, "dur": 1e6, "pid": 1,
            "tid": 0}]
    assert obs.stage_totals(evs, exclude=("outer",)) == {"leaf": 3.0}


# ---------------------------------------------------------------------------
# Metrics: snapshot + merge algebra
# ---------------------------------------------------------------------------


def _random_registry(rng: random.Random) -> obs.MetricsRegistry:
    reg = obs.MetricsRegistry()
    c = reg.counter("c")
    for _ in range(rng.randrange(4)):
        c.inc(rng.randrange(1, 10))
    g = reg.gauge("g")
    for _ in range(rng.randrange(4)):
        g.set(rng.uniform(-5, 5))
    h = reg.histogram("h", bounds=(1.0, 10.0, 100.0))
    for _ in range(rng.randrange(6)):
        h.observe(rng.uniform(0.1, 500.0))
    return reg


def _seeded_snapshots(seed: int, n: int = 3) -> list:
    rng = random.Random(seed)
    return [_random_registry(rng).snapshot() for _ in range(n)]


def _close(x, y, path="") -> None:
    """Snapshots must agree exactly on structure/ints and up to float
    rounding on sums (addition reassociates across merge orders)."""
    assert type(x) is type(y), f"{path}: {type(x)} vs {type(y)}"
    if isinstance(x, dict):
        assert x.keys() == y.keys(), path
        for k in x:
            _close(x[k], y[k], f"{path}/{k}")
    elif isinstance(x, list):
        assert len(x) == len(y), path
        for i, (a, b) in enumerate(zip(x, y, strict=True)):
            _close(a, b, f"{path}[{i}]")
    elif isinstance(x, float):
        assert x == pytest.approx(y, rel=1e-9), path
    else:
        assert x == y, path


def _merge_associative(snaps) -> None:
    a, b, c = snaps
    left = obs.merge_snapshots(obs.merge_snapshots(a, b), c)
    right = obs.merge_snapshots(a, obs.merge_snapshots(b, c))
    _close(left, right)
    flat = obs.merge_snapshots(a, b, c)
    assert flat == left


@pytest.mark.parametrize("seed", range(8))
def test_merge_snapshots_associative(seed):
    _merge_associative(_seeded_snapshots(seed))


if HAVE_HYPOTHESIS:                                    # pragma: no branch
    @settings(max_examples=50, deadline=None)
    @given(st.integers(min_value=0, max_value=10**9))
    def test_merge_snapshots_associative_hypothesis(seed):
        _merge_associative(_seeded_snapshots(seed))


def test_merged_snapshot_matches_single_registry_totals():
    reg1, reg2 = obs.MetricsRegistry(), obs.MetricsRegistry()
    both = obs.MetricsRegistry()
    for v, reg in ((3.0, reg1), (7.0, reg2)):
        reg.counter("n").inc(int(v))
        reg.gauge("q").set(v)
        reg.histogram("lat").observe(v)
        both.counter("n").inc(int(v))
        both.gauge("q").set(v)
        both.histogram("lat").observe(v)
    merged = obs.merge_snapshots(reg1.snapshot(), reg2.snapshot())
    assert merged == both.snapshot()


def test_histogram_merge_rejects_mismatched_bounds():
    a = obs.Histogram("h", bounds=(1.0, 2.0)).snapshot()
    b = obs.Histogram("h", bounds=(1.0, 3.0)).snapshot()
    da = {"schema": obs.METRICS_SCHEMA, "metrics": {"h": a}}
    db = {"schema": obs.METRICS_SCHEMA, "metrics": {"h": b}}
    with pytest.raises(ValueError):
        obs.merge_snapshots(da, db)


def test_histogram_percentile_brackets_samples():
    h = obs.Histogram("lat", bounds=obs.exp_buckets())
    for v in (0.001, 0.002, 0.004, 0.1):
        h.observe(v)
    assert h.percentile(1.0) == pytest.approx(0.1)
    assert 0.001 <= h.percentile(0.5) <= 0.1


# ---------------------------------------------------------------------------
# No-op neutrality: tracing must not change what it observes
# ---------------------------------------------------------------------------


def test_engine_round_bit_exact_tracing_on_and_off():
    s, K, L = 8, 6, 257
    f = get_field(s)
    P = f.random_elements(jax.random.PRNGKey(2), (K, L))
    eng = CodingEngine(EngineConfig(s=s, kernel="jnp_packed",
                                    chunk_l=64, extra_tuples=2))
    off = eng.round(P, jax.random.PRNGKey(9))
    tr = obs.set_tracer(obs.Tracer())
    try:
        on = eng.round(P, jax.random.PRNGKey(9))
    finally:
        obs.set_tracer(obs.NULL_TRACER)
    assert off.ok and on.ok
    np.testing.assert_array_equal(np.asarray(off.packets),
                                  np.asarray(on.packets))
    # the traced run really did record the per-stage spans (the fused
    # encode -> decode stream is one engine.decode span)
    names = {e["name"] for e in tr.events}
    assert {"engine.round", "engine.decode", "engine.invert"} <= names


def test_serve_trace_bit_exact_tracing_on_and_off():
    trace = poisson_multitenant_trace(4, 6, 32, s=8, rate=4.0,
                                      extra_packets=2, seed=3)
    off = serve_trace(trace, slots=4, g_tick=4, batched=True)
    tr = obs.set_tracer(obs.Tracer())
    try:
        on = serve_trace(trace, slots=4, g_tick=4, batched=True)
    finally:
        obs.set_tracer(obs.NULL_TRACER)
    assert [(c.job, c.arrivals, c.payload_sha)
            for c in off.completions] \
        == [(c.job, c.arrivals, c.payload_sha) for c in on.completions]
    assert obs.validate_trace(list(tr.events)) == []
    assert {e["name"] for e in tr.events} >= {"serve.ingest",
                                              "serve.queue_depth"}


def test_serve_metrics_snapshot_is_published():
    trace = poisson_multitenant_trace(4, 6, 32, s=8, rate=4.0,
                                      extra_packets=2, seed=3)
    rep = serve_trace(trace, slots=4, g_tick=4, batched=True)
    m = rep.metrics["metrics"]
    assert rep.metrics["schema"] == obs.METRICS_SCHEMA
    assert m["serve.ticks"]["value"] == rep.ticks
    assert m["serve.packets_ingested"]["value"] == rep.packets_ingested
    assert m["serve.job_latency_s"]["count"] == len(rep.completions)
    assert m["serve.queue_depth"]["count"] == rep.ticks


def test_disabled_tracer_overhead_under_2pct_of_serve_smoke():
    """The instrumentation bar: with tracing off, the per-call cost of
    the span/instant/counter paths and of the per-call record path (a
    record opened, a span, a count and a sample landed in it), times
    the number of events a traced smoke replay actually emits, must
    stay under 2% of that replay's wall time."""
    trace = poisson_multitenant_trace(6, 8, 64, s=8, rate=4.0,
                                      extra_packets=3, seed=5)
    serve_trace(trace, slots=4, g_tick=4, batched=True)   # jit warmup
    off = serve_trace(trace, slots=4, g_tick=4, batched=True)

    tr = obs.set_tracer(obs.Tracer())
    try:
        serve_trace(trace, slots=4, g_tick=4, batched=True)
    finally:
        obs.set_tracer(obs.NULL_TRACER)
    n_events = len(tr.events)

    null = obs.get_tracer()
    n = 100_000
    with obs.timed("overhead.null_span", tracer=None) as sw:
        for _ in range(n):
            with obs.record("overhead.x"):
                with null.span("x", cat="t", i=0):
                    obs.count("x")
                obs.sample("x", (0.0,))
            null.instant("x")
            null.counter("x", 1)
    per_call = sw.dur_s / n      # record + span + count + sample + ...
    overhead = per_call * n_events
    assert overhead < 0.02 * off.wall_s, (
        f"no-op instrumentation {overhead * 1e6:.1f}us vs "
        f"{off.wall_s * 1e3:.1f}ms replay ({n_events} events)")


# ---------------------------------------------------------------------------
# Host phases and per-call records
# ---------------------------------------------------------------------------


def _tiny_clients(K=4):
    keys = jax.random.split(jax.random.PRNGKey(3), K)
    return [{"w": jax.random.normal(k, (37,), jnp.float32),
             "b": jnp.arange(5, dtype=jnp.bfloat16) * (i + 1)}
            for i, k in enumerate(keys)]


_ROUNDS = {
    # (FedNCConfig kwargs, channel factory)
    "erasure": ({"kernel_impl": "jnp_packed", "extra_tuples": 2,
                 "chunk_l": 64}, lambda: ErasureChannel(0.2, seed=5)),
    "ideal_seeded": ({"kernel_impl": "jnp_packed_seeded", "chunk_l": 64},
                     lambda: None),
}


def _round(case, clients, seed=0):
    kw, channel = _ROUNDS[case]
    K = len(clients)
    return fednc_round(clients, [1.0 / K] * K, clients[0],
                       FedNCConfig(**kw), jax.random.PRNGKey(seed),
                       channel=channel())


@pytest.mark.parametrize("case", sorted(_ROUNDS))
def test_tracing_runs_the_same_programs_and_host_syncs(case, monkeypatch):
    """A traced and an untraced round make the same number of
    `jax.block_until_ready` calls, host reads of device values and
    kernel dispatches: no code path is taken only when tracing is on."""
    from jax._src.array import ArrayImpl

    from repro.core.fednc import engine_for

    calls = {"block_until_ready": 0, "host_read": 0}
    block, value = jax.block_until_ready, ArrayImpl._value

    def counting_block(x):
        calls["block_until_ready"] += 1
        return block(x)

    def counting_value(self):
        calls["host_read"] += 1
        return value.fget(self)

    clients = _tiny_clients()
    eng = engine_for(FedNCConfig(**_ROUNDS[case][0]))
    _round(case, clients)                      # compile outside the count
    monkeypatch.setattr(jax, "block_until_ready", counting_block)
    monkeypatch.setattr(ArrayImpl, "_value", property(counting_value))
    seen = {}
    for traced in (False, True):
        obs.set_tracer(obs.Tracer() if traced else obs.NULL_TRACER)
        calls.update(block_until_ready=0, host_read=0)
        d0 = eng.dispatch_count
        res = _round(case, clients, seed=1)
        assert res.decoded
        rec = obs.last_records("fednc.round", 1)[0]
        seen[traced] = (dict(calls), eng.dispatch_count - d0,
                        rec.counts, sorted(rec.spans))
    assert seen[False] == seen[True]
    assert seen[False][2]["fednc.syncs"] >= 1
    assert seen[False][2]["engine.dispatches"] == seen[False][1] >= 2


def test_span_lands_on_the_profiler_host_plane(tmp_path):
    """Spans are TraceAnnotations: a CPU profile of a round holds them by
    name, as the chip benchmark's trace reader sees them."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from chipbench import profile

    clients = _tiny_clients()
    _round("erasure", clients)                 # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        _round("erasure", clients, seed=1)
    finally:
        jax.profiler.stop_trace()
    pb = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    trace = profile.read(pb[0], span_prefix="fednc.")
    names = {n for n, _, _ in trace.spans}
    assert {"fednc.packetize", "fednc.sync", "fednc.aggregate"} <= names
    assert all(e > s for _, s, e in trace.spans)


@pytest.mark.parametrize("case", sorted(_ROUNDS))
def test_fednc_round_leaves_one_record_per_call(case):
    clients = _tiny_clients()
    t0 = obs.clock()
    for seed in range(3):
        _round(case, clients, seed=seed)
    recs = [r for r in obs.records("fednc.round") if r.t0 >= t0]
    assert len(recs) == 3
    phases = ("fednc.packetize", "engine.round", "engine.decode",
              "fednc.sync", "fednc.aggregate")
    for rec in recs:
        assert all(rec.spans[p] > 0 for p in phases), rec.spans
        assert rec.t1 > rec.t0
        assert rec.span_ms("fednc.packetize", "fednc.aggregate") \
            < 1e3 * (rec.t1 - rec.t0)
        assert rec.counts["fednc.syncs"] >= 1


def test_aggregate_compiles_once_per_spec_and_cohort():
    """`fednc.aggregate_traces` counts traces of the compiled aggregate:
    1 for a new tree, 0 for new weights on the same tree and K."""
    clients = [{"v": jnp.full((43,), i + 1.0, jnp.float32)}
               for i in range(3)]
    cfg = FedNCConfig(kernel_impl="jnp_packed", chunk_l=64)
    other_tree = [{"v": c["v"][:41]} for c in clients]
    traces = []
    for weights, cs in (([1.0, 1.0, 1.0], clients),
                        ([0.2, 0.3, 0.5], clients),
                        ([1.0, 1.0, 1.0], other_tree)):
        res = fednc_round(cs, weights, cs[0], cfg, jax.random.PRNGKey(7))
        assert res.decoded
        rec = obs.last_records("fednc.round", 1)[0]
        assert rec.spans["fednc.aggregate"] > 0
        traces.append(rec.counts["fednc.aggregate_traces"])
    assert traces == [1, 0, 1]


def test_record_ring_is_bounded_and_nested_entries_open_one_record():
    with obs.record("outer.call") as outer:
        _round("erasure", _tiny_clients())     # a nested entry point
        with obs.record("inner.call") as inner:
            obs.count("inner.items", 2)
    assert inner is outer
    last = obs.records()[-1]
    assert last is outer and last.name == "outer.call"
    assert last.counts["inner.items"] == 2
    assert last.spans["fednc.packetize"] > 0   # the round's phases
    assert obs.records("inner.call") == []
    for _ in range(obs.RECORDS_MAX + 10):
        with obs.record("filler.call"):
            pass
    assert len(obs.records()) == obs.RECORDS_MAX
    assert obs.last_records("filler.call", obs.RECORDS_MAX) is not None
    assert obs.last_records("outer.call", 1) is None     # fell out


def test_serve_replay_leaves_one_record_per_tick():
    trace = poisson_multitenant_trace(4, 6, 32, s=8, rate=4.0,
                                      extra_packets=2, seed=3)
    t0 = obs.clock()
    rep = serve_trace(trace, slots=4, g_tick=4, batched=True)
    recs = [r for r in obs.records("serve.tick") if r.t0 >= t0]
    assert len(recs) == rep.ticks
    valid = sum(r.counts["serve.packets_ingested"] for r in recs)
    capacity = sum(r.counts["serve.block_capacity"] for r in recs)
    waits = [w for r in recs for w in r.samples["serve.queue_wait_s"]]
    assert valid == rep.packets_ingested == len(waits)
    assert capacity == rep.ticks * 4 * 4 >= valid
    assert min(waits) >= 0.0
    for r in recs:
        assert len(r.samples["serve.queue_wait_s"]) \
            == r.counts["serve.packets_ingested"]
        assert all(r.spans[p] > 0 for p in (
            "serve.schedule", "serve.ingest", "serve.readback",
            "serve.complete"))


# ---------------------------------------------------------------------------
# Grid: scenario-local tracing + spawn-context merge
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_run_grid_jobs2_merges_worker_traces(tmp_path):
    """run_grid(jobs=2) spawn workers each record into their own
    tracer; the parent must merge the lanes into one valid trace with
    one pid per worker and per-scenario per_stage breakdowns."""
    from repro.grid import GridAxes, run_grid
    pytest.importorskip("multiprocessing")
    specs = GridAxes(strategy=("fednc_stream", "fedavg"),
                     straggler=("exponential",), population=(300,),
                     clients_per_round=8, rounds=2).expand()
    path = tmp_path / "TRACE_grid.json"
    results = run_grid(specs, jobs=2, trace_path=path)
    assert len(results) == 2
    for entry in results.values():
        assert entry["per_stage"].get("sim.round", 0.0) > 0.0
    events = obs.load_trace(path)
    assert obs.validate_trace(events) == []
    pids = {e["pid"] for e in events}
    assert len(pids) == 2, f"expected one pid lane per worker: {pids}"
