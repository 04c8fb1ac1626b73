"""Decode-server cells: an open loop driving ``repro.serve.DecodeServer``.

Set-up draws k clients' updates from the seed, cuts each into l-byte
segments (generation g carries segment g mod n_seg of every client),
builds the window's arrival schedule (``arrivals.open_loop``) and
encodes every packet on the device with the benchmark's own GF(2^8)
(``C = rows(seed) · P``, rows from ``reference/threefry.py``).  It then
warms the server with 2 × slots generations offered at once, which
opens, fills, completes and reopens every slot.

The window offers each packet when it is due, through ``submit`` and
``offer`` (seeded packets ship the seed, materialized ones the row), and
calls ``tick`` whenever packets are pending.  A generation's lag is the
end of the tick that brought it to rank k minus the due time of the
packet that did.  After ``seconds`` the loop goes on offering and
ticking until every generation has completed, or ``drain_s`` more
seconds have passed: a late answer is late, not wrong.

The check compares every completed generation's decoded (k, l) payload
with its source segment, byte for byte.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import arrivals
from chipbench.harness import check as _check
from chipbench.harness import clock
from chipbench.reference import gf, threefry

TIMED = ("bench.window",)
PHASES = ("bench.offer", "bench.tick", "bench.wait")
BLOCK = 256          # generations per encode call (one compiled shape)


def segments(config: dict, k: int, l: int, seed: int) -> np.ndarray:
    """(n_seg, k, l) uint8: the k clients' update bytes, cut into l-byte
    segments (the last one zero-padded)."""
    upd = config["update"]
    per = []
    for c in range(k):
        x = arrivals.rng_for(seed, 61, c).standard_normal(int(upd["params"]))
        b = (float(upd["scale"]) * x).astype("<f4").view(np.uint8)
        per.append(np.pad(b, (0, (-b.size) % l)).reshape(-1, l))
    return np.stack(per, axis=1)


def _xtime(a):
    return ((a << 1) ^ jnp.where(a & 0x80, gf.POLY[8], 0)) & 0xFF


@jax.jit
def _encode_block(A, P):
    """(B, n, k) rows · (B, k, l) segments over GF(2^8) -> (B, n, l)."""
    A = A.astype(jnp.int32)
    P = P.astype(jnp.int32)
    acc = jnp.zeros((A.shape[0], A.shape[1], P.shape[2]), jnp.int32)
    for j in range(A.shape[2]):
        a = jnp.broadcast_to(A[:, :, j, None], acc.shape)
        b = jnp.broadcast_to(P[:, None, j, :], acc.shape)
        for i in range(8):
            acc = acc ^ jnp.where((b >> i) & 1, a, 0)
            a = _xtime(a)
    return acc.astype(jnp.uint8)


def encode(rows: np.ndarray, segs: np.ndarray, gens: np.ndarray
           ) -> np.ndarray:
    """Coded payloads (G, n, l) of generations `gens` on the device."""
    G = rows.shape[0]
    Gp = -(-G // BLOCK) * BLOCK
    rows = np.pad(rows, ((0, Gp - G), (0, 0), (0, 0)))
    P = segs[np.pad(gens, (0, Gp - G)) % segs.shape[0]]
    out = [np.asarray(_encode_block(rows[b:b + BLOCK], P[b:b + BLOCK]))
           for b in range(0, Gp, BLOCK)]
    return np.concatenate(out)[:G]


@dataclass
class Traffic:
    """One schedule, its rows and coded payloads, and job ids."""

    ol: arrivals.OpenLoop
    rows: np.ndarray            # (G, n, k)
    C: np.ndarray               # (G, n, l)
    first_job: int

    def offer(self, srv, g: int, j: int) -> None:
        job = self.first_job + g
        sd = int(self.ol.seeds[g, j])
        if self.ol.seeded[g]:
            srv.offer(job, self.C[g, j], seed=sd)
        else:
            srv.offer(job, self.C[g, j], seed=sd, row=self.rows[g, j])


def _traffic(ctx, segs, seconds, stream, first_job, rate) -> Traffic:
    tr = ctx.traffic
    ol = arrivals.open_loop(tr, ctx.seed, seconds, rate=rate, stream=stream)
    rows = threefry.rows(ol.seeds.reshape(-1), ol.k, 8).reshape(
        ol.gens, ol.n, ol.k)
    C = encode(rows, segs, np.arange(first_job, first_job + ol.gens))
    return Traffic(ol, rows, C, first_job)


class ControlServer:
    """The plain decoder in the program's place, over GF(2^4): the
    4-bit field below the configuration's GF(2^8)."""

    def __init__(self, k: int):
        self.k = k
        self.jobs: dict = {}
        self.pending: list = []
        self.ticks = 0

    def submit(self, job, k, l=None):
        self.jobs[job] = SimpleNamespace(rows=[], pay=[], done=None,
                                         payload=None)

    def offer(self, job, payload, *, seed=0, row=None):
        a = threefry.rows([seed], self.k, 4)[0] if row is None \
            else np.asarray(row) & 0xF
        self.pending.append((job, a, np.asarray(payload) & 0xF))

    def tick(self) -> bool:
        if not self.pending:
            return False
        touched = set()
        for job, a, c in self.pending:
            st = self.jobs[job]
            if st.done is None:
                st.rows.append(a)
                st.pay.append(c)
                touched.add(job)
        self.pending = []
        for job in touched:
            st = self.jobs[job]
            P, used = gf.solve_first_rank(st.rows, st.pay, self.k, s=4)
            if P is not None:
                st.done, st.payload = SimpleNamespace(arrivals=used), P
        self.ticks += 1
        return True

    def completion(self, job):
        return self.jobs[job].done

    def result(self, job):
        return self.jobs[job].payload


@dataclass
class State:
    ctx: object
    srv: object
    segs: np.ndarray
    traffic: Traffic
    rate: float


def _drive(srv, tf: Traffic, seconds: float, drain_s: float,
           annotate=None) -> dict:
    """The open loop over one schedule; returns per-packet offer times
    and per-generation completion ticks (seconds from the start)."""
    import contextlib
    annotate = annotate or (lambda name: contextlib.nullcontext())
    ol = tf.ol
    G, n = ol.gens, ol.n
    due = ol.due.reshape(-1)
    N = G * n
    offered = np.full(N, np.nan)
    done_at = np.full(G, np.nan)
    used = np.zeros(G, np.int64)
    submitted = np.zeros(G, bool)
    open_gens: set = set()
    inflight = []
    ticks = 0
    i = 0
    t0 = clock()
    with annotate("bench.window"):
        while open_gens or i < N:
            now = clock() - t0
            if now > seconds + drain_s:
                break
            with annotate("bench.offer"):
                while i < N and due[ol.order[i]] <= now:
                    p = int(ol.order[i])
                    g, j = divmod(p, n)
                    if not submitted[g]:
                        srv.submit(tf.first_job + g, ol.k)
                        submitted[g] = True
                        open_gens.add(g)
                    tf.offer(srv, g, j)
                    offered[p] = clock() - t0
                    i += 1
            with annotate("bench.tick"):
                busy = srv.tick()
            if busy:
                ticks += 1
                t_end = clock() - t0
                for g in list(open_gens):
                    c = srv.completion(tf.first_job + g)
                    if c is not None:
                        done_at[g], used[g] = t_end, c.arrivals
                        open_gens.discard(g)
                inflight.append((t_end, len(open_gens)))
            elif i < N:
                with annotate("bench.wait"):
                    time.sleep(max(0.0, due[ol.order[i]] - (clock() - t0)))
            else:
                break       # every packet offered and ingested
    return {"offered": offered, "done_at": done_at, "used": used,
            "ticks": ticks, "inflight": inflight}


def setup(ctx) -> State:
    from repro.serve import DecodeServer

    tr = ctx.traffic
    k, l = int(tr["k"]), int(tr["l"])
    segs = segments(ctx.config, k, l, ctx.seed)
    rate = ctx.rate or float(tr["rate_gen_per_s"])
    srv = ControlServer(k) if ctx.control else DecodeServer(
        int(tr["slots"]), k, l, s=int(ctx.config["s"]),
        g_tick=int(tr["g_tick"]))
    # warm-up: 2 x slots generations offered at once, ticked to the end
    n_warm = 2 * int(tr["slots"])
    warm = _traffic(ctx, segs, (n_warm + 0.5) / rate, 1, 1 << 30, rate)
    warm.ol.due[:] = 0.0
    _drive(srv, warm, 0.0, 600.0)
    return State(ctx, srv, segs,
                 _traffic(ctx, segs, ctx.seconds, 0, 0, rate), rate)


def window(state: State, seconds: float) -> dict:
    ctx = state.ctx
    srv, tf = state.srv, state.traffic
    ticks0 = getattr(srv, "ticks", 0)
    run = _drive(srv, tf, seconds, ctx.drain_s, ctx.annotate)
    ol = tf.ol
    G = ol.gens
    # a generation's lag: end of its completing tick - due time of the
    # packet that brought it to rank k (missing: infinite)
    due_k = np.where(run["used"] > 0,
                     ol.due[np.arange(G), np.maximum(run["used"], 1) - 1],
                     np.inf)
    lag = np.where(np.isnan(run["done_at"]), np.inf,
                   run["done_at"] - due_k)
    in_window = due_k <= seconds
    missing = np.isnan(run["done_at"])
    k, l = int(ctx.traffic["k"]), int(ctx.traffic["l"])
    inflight = run["inflight"]
    third = [v for t, v in inflight if t <= seconds / 3]
    last = [v for t, v in inflight if 2 * seconds / 3 <= t <= seconds]
    return {"window_s": seconds, "gens": G,
            "lag_s": lag[in_window | missing],
            "gen_lag_s": (run["offered"] - ol.due.reshape(-1))[
                ~np.isnan(run["offered"])],
            "done_in_window": int(np.sum(run["done_at"] <= seconds)),
            "decoded_bytes": int(np.sum(run["done_at"] <= seconds)) * k * l,
            "ticks": getattr(srv, "ticks", 0) - ticks0,
            "loop_ticks": run["ticks"],
            "done_at": run["done_at"],
            "inflight_first_third": float(np.mean(third)) if third else 0.0,
            "inflight_last_third": float(np.mean(last)) if last else 0.0,
            "rate": state.rate}


def describe(obs: dict) -> dict:
    lag = obs["lag_s"]
    fin = lag[np.isfinite(lag)]
    return {"gens": obs["gens"], "lag_samples": int(lag.size),
            "missing": int(np.sum(~np.isfinite(lag))),
            "lag_p50_ms": float(np.median(fin) * 1e3) if fin.size else None,
            "done_in_window": obs["done_in_window"], "ticks": obs["ticks"],
            "rate": obs["rate"],
            "inflight_first_third": obs["inflight_first_third"],
            "inflight_last_third": obs["inflight_last_third"]}


def check(state: State, obs: dict):
    tf = state.traffic
    wrong = missing = checked = 0
    for g in range(tf.ol.gens):
        if np.isnan(obs["done_at"][g]):
            missing += 1
            continue
        got = np.asarray(state.srv.result(tf.first_job + g))
        src = state.segs[g % state.segs.shape[0]]
        wrong += int(not np.array_equal(got, src))
        checked += 1
    checks = [_check("gens_wrong", wrong, 0, "<="),
              _check("gens_missing", missing, 0, "<="),
              _check("gens_checked", checked, 1, ">=")]
    return checks, tf.ol.gens, wrong + missing
