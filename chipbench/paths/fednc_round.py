"""Coded-round cells: ``fednc_round`` over K clients' updates, back to back.

Set-up makes the K client updates on the device in one jitted call from
the seed, at the configuration's leaf shapes and dtypes, and warms every
shape the window uses: one full-size round, and a small round for each
other number of delivered tuples the channel can leave (each is a new
``(delivered, K)`` selection shape).

The window runs round attempts until ``seconds`` of them have passed;
the attempt in flight then finishes and counts.  Each attempt draws its
own PRNG key and erasure pattern from the seed; one that leaves fewer
than K independent tuples is retried with the next, as an aggregator
would, and its time stays in the window.  Between attempts, off the
clock, each decoded aggregate is copied to the host and digested.

The check digests the plain FedAvg mean of the same updates
(``reference/fedavg.py``) and counts the rounds whose aggregate differs.
"""
from __future__ import annotations

from dataclasses import dataclass
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.arrivals import u32
from chipbench.harness import check as _check
from chipbench.harness import clock
from chipbench.reference import fedavg

TIMED = ("bench.round",)
PHASES = ("bench.round",)


@dataclass
class State:
    ctx: object
    cfg: object
    clients: list
    weights: list
    K: int
    L: int
    p_erase: float
    n: int


def make_clients(leaves, K: int, seed: int, scale: float) -> list:
    """K updates (lists of leaves) drawn on the device: one compiled
    program makes a whole update, called once per client."""
    shapes = [(tuple(sh), jnp.dtype(dt)) for _, sh, dt in leaves]

    @jax.jit
    def make(key):
        return [(scale * jax.random.normal(jax.random.fold_in(key, i), sh,
                                           jnp.float32)).astype(dt)
                for i, (sh, dt) in enumerate(shapes)]

    root = jax.random.PRNGKey(np.uint32(seed))
    return jax.block_until_ready(
        [make(jax.random.fold_in(root, k)) for k in range(K)])


def _channel(p_erase: float, chan_seed: int):
    from repro.core.channel import ErasureChannel
    return ErasureChannel(p_erase, seed=chan_seed) if p_erase > 0 else None


def _program_round(state: State, clients: list, key_seed: int,
                   chan_seed: int):
    """One call of the entry point under test."""
    from repro.core.fednc import fednc_round
    return fednc_round(clients, state.weights, clients[0], state.cfg,
                       jax.random.PRNGKey(np.uint32(key_seed)),
                       channel=_channel(state.p_erase, chan_seed))


def _control_round(state: State, clients: list, key_seed: int,
                   chan_seed: int):
    """The plain FedAvg mean on the device, accumulated in bfloat16 —
    one precision below the configuration's float32.  Every partial sum
    is rounded to bfloat16 explicitly: XLA on a TPU would otherwise keep
    a fused chain of bfloat16 ops in float32."""
    w = fedavg.weights(len(clients))

    def bf16(x):
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)

    def mean(*xs):
        acc = 0
        for wk, x in zip(w, xs, strict=True):
            acc = bf16(acc + bf16(jnp.float32(wk) * bf16(x.astype(jnp.float32))))
        return acc.astype(xs[0].dtype)

    agg = jax.jit(lambda cs: jax.tree_util.tree_map(mean, *cs))(clients)
    return SimpleNamespace(global_params=agg, decoded=True)


def _round(state: State, clients: list, attempt_seed: tuple):
    fn = _control_round if state.ctx.control else _program_round
    res = fn(state, clients, *attempt_seed)
    jax.block_until_ready(res.global_params)
    return res


def _delivered_seed(p_erase: float, n: int, s: int, want: int,
                    seed: int) -> int:
    """A channel seed whose erasure pattern delivers `want` of n tuples."""
    for i in range(100_000):
        cs = u32(seed, 43, i)
        if len(_channel(p_erase, cs).plan_transform(n, s).idx) == want:
            return cs
    raise RuntimeError(f"no channel seed delivers {want} of {n}")


def setup(ctx) -> State:
    from repro.core.fednc import FedNCConfig

    cfg_, tr = ctx.config, ctx.traffic
    K, s = int(tr["clients"]), int(ctx.config["s"])
    cfg = FedNCConfig(s=s, kernel_impl=tr["kernel"],
                      extra_tuples=int(tr["extra_tuples"]))
    leaves = cfg_["update"]["leaves"]
    clients = make_clients(leaves, K, u32(ctx.seed, 7),
                           float(cfg_["update"]["scale"]))
    n_bytes = sum(int(np.prod(sh)) * jnp.dtype(dt).itemsize
                  for _, sh, dt in leaves)
    state = State(ctx, cfg, clients, [1.0 / K] * K, K,
                  n_bytes * (8 // s), float(tr["p_erase"]),
                  K + int(tr["extra_tuples"]))
    # warm-up: the full-size round where every tuple arrives, then a
    # small round for each other decodable delivery count
    full = _delivered_seed(state.p_erase, state.n, s, state.n, ctx.seed) \
        if state.p_erase > 0 else 0
    _round(state, clients, (u32(ctx.seed, 47), full))
    if state.p_erase > 0:
        small = [[jnp.zeros((256,), jnp.uint8)] for _ in range(K)]
        for d in range(K, state.n + 1):
            _round(state, small, (u32(ctx.seed, 53, d), _delivered_seed(
                state.p_erase, state.n, s, d, ctx.seed)))
    return state


def _digest(tree) -> str:
    return fedavg.digest(np.asarray(x) for x in jax.tree_util.tree_leaves(tree))


def window(state: State, seconds: float) -> dict:
    from repro.core.fednc import engine_for

    eng = engine_for(state.cfg)
    d0 = eng.dispatch_count
    elapsed, attempts, digests = 0.0, 0, []
    while elapsed < seconds:
        seeds = (u32(state.ctx.seed, 31, attempts),
                 u32(state.ctx.seed, 37, attempts))
        with state.ctx.annotate("bench.round"):
            t = clock()
            res = _round(state, state.clients, seeds)
            elapsed += clock() - t
        attempts += 1
        if res.decoded:
            with state.ctx.annotate("bench.check"):
                digests.append(_digest(res.global_params))
        del res
    return {"window_s": elapsed, "attempts": attempts,
            "rounds": len(digests), "digests": digests,
            "dispatches": eng.dispatch_count - d0,
            "K": state.K, "L": state.L}


def describe(obs: dict) -> dict:
    return {k: obs[k] for k in ("window_s", "attempts", "rounds",
                                "dispatches", "K", "L")}


def check(state: State, obs: dict):
    clients = state.clients
    ref = fedavg.mean_digest(len(clients[0]),
                             lambda k, i: np.asarray(clients[k][i]), state.K)
    wrong = sum(d != ref for d in obs["digests"])
    checks = [_check("rounds_wrong", wrong, 0, "<="),
              _check("rounds_checked", len(obs["digests"]), 1, ">=")]
    return checks, len(obs["digests"]), wrong
