"""Expert-selective cross-silo rounds: ``fednc_round`` over K silos that
each upload the dense part of the model and only the experts they
trained, back to back.

The configuration's ``experts`` names the blocked leaves (routed experts
stacked as (MoE layers, experts held here, ...)), their grid, the expert
ids this chip holds, and how many experts a layer has (``of``).  Set-up
draws the selection from the traffic's ``selection_seed``, not the run
seed, so every run codes the same generations: in each MoE layer each
silo trains ``trained_per_layer`` of the ``of`` experts, drawn without
replacement with probability ∝ 1/rank over a per-layer rank permutation.
Silo k uploads, of the experts this chip holds, those it trained
(``packets.Blocks``), and no buffer for the others.  Its whole leaves,
its expert rows (one draw per block) and the previous global model are
drawn on the device from the run seed.  Warm-up is one decoded round,
which compiles every shape the window uses.

The window runs round attempts as ``paths/fednc_round.py`` does: each
attempt draws its key and erasure pattern from the seed; one that leaves
a generation short of rank is retried with the next, and its time stays
in the window.  Off the clock each decoded aggregate is copied to the
host and digested.

The check digests the per-block FedAvg mean (``reference/fedavg_blocks.py``)
and counts the rounds whose aggregate differs.  The control
(``--control 1``) puts a wrong mean, computed on the device, in the
program's place, alternating two kinds by attempt: the per-block mean
accumulated in bfloat16, one precision below the configuration's
float32, and every expert averaged over all K silos with the experts a
silo did not train read as zeros.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.arrivals import u32
from chipbench.harness import check as _check
from chipbench.harness import clock
from chipbench.reference import fedavg, fedavg_blocks

TIMED = ("bench.round",)
PHASES = ("bench.round",)
CONTROLS = ("bf16_mean", "absent_as_zero")


@dataclass
class State:
    ctx: object
    cfg: object
    clients: list         # K dicts: leaf name -> array, or Blocks
    prev: dict            # leaf name -> the previous global value
    names: list           # leaf names in the trees' leaf order
    blocked: dict         # leaf position -> (grid, holders of each block)
    held: list            # held[k]: the block ids silo k uploads
    weights: list
    K: int
    L: int                # symbols of the dense part a silo uploads
    coded_symbols: int    # Σ_g K_g·L_g over the round's generations
    p_erase: float
    n: list               # tuples sent per generation


def trained(of: int, per_layer: int, layers: int, K: int,
            seed: int) -> list:
    """``sel[l][k]``: the experts silo k trains in MoE layer l (a set)."""
    rng = np.random.default_rng(seed)
    sel = []
    for _ in range(layers):
        p = 1.0 / (rng.permutation(of) + 1)
        p /= p.sum()
        sel.append([set(rng.choice(of, per_layer, replace=False,
                                   p=p).tolist()) for _ in range(K)])
    return sel


@functools.lru_cache(maxsize=None)
def _normal(shape: tuple, dtype: str, scale: float):
    return jax.jit(lambda key: (scale * jax.random.normal(
        key, shape, jnp.float32)).astype(dtype))


def _channel(p_erase: float, chan_seed: int):
    from repro.core.channel import ErasureChannel
    return ErasureChannel(p_erase, seed=chan_seed) if p_erase > 0 else None


def _program_round(state: State, key_seed: int, chan_seed: int, _):
    """One call of the entry point under test."""
    from repro.core.fednc import fednc_round
    return fednc_round(state.clients, state.weights, state.prev, state.cfg,
                       jax.random.PRNGKey(np.uint32(key_seed)),
                       channel=_channel(state.p_erase, chan_seed))


@functools.partial(jax.jit, static_argnums=2)
def _mean(xs, w, bf16: bool):
    """Σ_k w_k·x_k from 0 in float32, or with every partial sum and
    product rounded to bfloat16 (XLA on a TPU would otherwise keep a
    fused chain of bfloat16 ops in float32)."""
    r = (lambda x: jax.lax.reduce_precision(x, exponent_bits=8,
                                            mantissa_bits=7)) \
        if bf16 else (lambda x: x)
    acc = 0
    for wk, x in zip(w, xs, strict=True):
        acc = r(acc + r(wk * r(x.astype(jnp.float32))))
    return acc.astype(xs[0].dtype)


def _control_round(state: State, key_seed: int, chan_seed: int,
                   attempt: int):
    """A wrong mean on the device, in the program's place (module doc)."""
    kind = CONTROLS[attempt % len(CONTROLS)]
    K, w = state.K, np.asarray(state.weights, np.float32)
    w_all = jnp.asarray(w / w.sum())
    out = {}
    for i, name in enumerate(state.names):
        xs = [c[name] for c in state.clients]
        if i not in state.blocked:
            out[name] = _mean(tuple(xs), w_all, kind == "bf16_mean")
            continue
        grid, holders = state.blocked[i]
        leaf = state.prev[name]
        for b, hs in enumerate(holders):
            if kind == "bf16_mean" and hs:
                rows = tuple(xs[k].rows[state.held[k].index(b)] for k in hs)
                ws = jnp.asarray(fedavg_blocks.block_weights(w, hs))
                x = _mean(rows, ws, True)
            elif kind == "absent_as_zero":
                zero = jnp.zeros(xs[0].rows.shape[1:], xs[0].rows.dtype)
                rows = tuple(xs[k].rows[state.held[k].index(b)]
                             if k in hs else zero for k in range(K))
                x = _mean(rows, w_all, False)
            else:
                continue
            leaf = leaf.at[np.unravel_index(b, grid)].set(x)
        out[name] = leaf
    return SimpleNamespace(global_params=out, decoded=True, kind=kind)


def _round(state: State, seeds: tuple, attempt: int):
    fn = _control_round if state.ctx.control else _program_round
    res = fn(state, *seeds, attempt)
    jax.block_until_ready(res.global_params)
    return res


def _decodable_seed(state: State, s: int, seed: int) -> int:
    """A channel seed whose erasure pattern leaves every generation at
    least K_g tuples (the generations draw their patterns in order)."""
    for i in range(100_000):
        cs = u32(seed, 43, i)
        ch = _channel(state.p_erase, cs)
        if all(len(ch.plan_transform(n, s).idx) >= n - state.cfg.extra_tuples
               for n in state.n):
            return cs
    raise RuntimeError("no channel seed lets every generation decode")


def setup(ctx) -> State:
    from repro.core.fednc import FedNCConfig
    from repro.core.packets import Blocks   # a program without partial updates stops here

    cf, tr = ctx.config, ctx.traffic
    K, s = int(tr["clients"]), int(cf["s"])
    ex = cf["experts"]
    grid, chip = tuple(ex["grid"]), list(ex["held"])
    sel = trained(int(ex["of"]), int(tr["trained_per_layer"]), grid[0], K,
                  int(tr["selection_seed"]))
    # flat block id l·len(chip) + j: expert chip[j] of MoE layer l
    held = [[l * len(chip) + j for l in range(grid[0])
             for j, e in enumerate(chip) if e in sel[l][k]]
            for k in range(K)]
    holders = [tuple(k for k in range(K) if b in held[k])
               for b in range(int(np.prod(grid)))]
    leaves = {n: (tuple(sh), dt) for n, sh, dt in cf["update"]["leaves"]}
    names = sorted(leaves)
    scale = float(cf["update"]["scale"])
    root = jax.random.PRNGKey(np.uint32(u32(ctx.seed, 7)))

    def draw(owner, i, shape, dtype, b=0):
        key = jax.random.fold_in(jax.random.fold_in(
            jax.random.fold_in(root, owner), i), b)
        return _normal(shape, dtype, scale)(key)

    clients = []
    for k in range(K):
        tree = {}
        for i, name in enumerate(names):
            shape, dtype = leaves[name]
            if name in ex["leaves"]:
                rows = [draw(k, i, shape[len(grid):], dtype, b)
                        for b in held[k]]
                tree[name] = Blocks(
                    jnp.stack(rows) if rows else
                    jnp.zeros((0, *shape[len(grid):]), dtype),
                    held[k], grid)
            else:
                tree[name] = draw(k, i, shape, dtype)
        clients.append(jax.block_until_ready(tree))
    prev = jax.block_until_ready({name: draw(K, i, *leaves[name])
                                  for i, name in enumerate(names)})
    dense = [n for n in names if n not in ex["leaves"]]
    L = sum(int(np.prod(leaves[n][0])) * jnp.dtype(leaves[n][1]).itemsize
            for n in dense) * (8 // s)
    L_b = sum(int(np.prod(leaves[n][0][len(grid):]))
              * jnp.dtype(leaves[n][1]).itemsize
              for n in ex["leaves"]) * (8 // s)
    extra = int(tr["extra_tuples"])
    Ks = [K] + [len(h) for h in holders if h]
    state = State(
        ctx, FedNCConfig(s=s, kernel_impl=tr["kernel"], extra_tuples=extra),
        clients, prev, names,
        {names.index(n): (grid, holders) for n in ex["leaves"]},
        held, [float(x) for x in tr["weights"]], K, L,
        K * L + sum(Ks[1:]) * L_b, float(tr["p_erase"]),
        [k + extra for k in Ks])
    # warm-up: one decoded round compiles every shape the window uses
    chan = _decodable_seed(state, s, ctx.seed) if state.p_erase > 0 else 0
    for i in range(20):
        if _round(state, (u32(ctx.seed, 47, i), chan), 0).decoded:
            break
    return state


def _digest(tree) -> str:
    return fedavg.digest(np.asarray(x) for x in jax.tree_util.tree_leaves(tree))


def window(state: State, seconds: float) -> dict:
    from repro.core.fednc import engine_for

    eng = engine_for(state.cfg)
    d0 = eng.dispatch_count
    elapsed, attempts, digests, kinds = 0.0, 0, [], []
    while elapsed < seconds:
        seeds = (u32(state.ctx.seed, 31, attempts),
                 u32(state.ctx.seed, 37, attempts))
        with state.ctx.annotate("bench.round"):
            t = clock()
            res = _round(state, seeds, attempts)
            elapsed += clock() - t
        attempts += 1
        if res.decoded:
            with state.ctx.annotate("bench.check"):
                digests.append(_digest(res.global_params))
            kinds.append(getattr(res, "kind", None))
        del res
    return {"window_s": elapsed, "attempts": attempts,
            "rounds": len(digests), "digests": digests, "kinds": kinds,
            "dispatches": eng.dispatch_count - d0, "K": state.K,
            "L": state.L, "coded_symbols": state.coded_symbols,
            "generations": len(state.n)}


def describe(obs: dict) -> dict:
    return {k: obs[k] for k in ("window_s", "attempts", "rounds",
                                "dispatches", "K", "L", "coded_symbols",
                                "generations")}


def check(state: State, obs: dict):
    def block(k, i, b):
        x = state.clients[k][state.names[i]]
        return np.asarray(x.rows[state.held[k].index(b)])

    ref = fedavg_blocks.mean_digest(
        len(state.names), state.blocked, state.weights,
        lambda k, i: np.asarray(state.clients[k][state.names[i]]),
        lambda i: np.asarray(state.prev[state.names[i]]), block)
    wrong = [d != ref for d in obs["digests"]]
    checks = [_check("rounds_wrong", sum(wrong), 0, "<="),
              _check("rounds_checked", len(wrong), 1, ">=")]
    if state.ctx.control:
        checks += [_check(f"rounds_wrong.{kind}",
                          sum(x for x, k in zip(wrong, obs["kinds"],
                                                strict=True) if k == kind),
                          0, "<=")
                   for kind in CONTROLS]
    return checks, len(wrong), sum(wrong)
