"""Lane-sharded coded-round cells: ``CodingEngine.round`` on a mesh, back
to back.

``fednc_round`` has no mesh path, so this entry times the engine round
alone: draw, select and invert the (n, K) coding matrix, then encode and
decode the (K, L) payload chunk by chunk, each device on its own lanes.
There is no packetize and no aggregate.

Set-up builds a 1-D ``("data",)`` mesh over the cell's devices and the
payload one silo at a time: one jitted call draws the silo's update
from the seed at the configuration's leaves and packetizes it
(``pytree_to_packet``) on the first device; the row is spread over the
mesh and freed, so no device ever holds more than one whole row; the
rows are then stacked lane-sharded.  Warm-up is one round and one check.

The window runs rounds until ``seconds`` of them have passed, each with
its own PRNG key from the seed; the round in flight then finishes and
counts.  Between rounds, off the clock, the decoded packets are
compared with the payload on the devices (one bool to the host): coding
is lossless, so every decoded round returns the payload bit for bit.

The control (``--control 1``) decodes with the inverse of the coding
matrix taken over GF(2^4), the field below the configuration's GF(2^8),
by the benchmark's own field arithmetic (``reference/gf.py``).
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.arrivals import u32
from chipbench.harness import check as _check
from chipbench.harness import clock
from chipbench.reference import gf

TIMED = ("bench.round",)
PHASES = ("bench.round",)


@dataclass
class State:
    ctx: object
    engine: object
    P: object             # (K, L) uint8 payload, lanes sharded
    same: object          # jitted (a, b) -> a == b everywhere
    K: int
    L: int


def _row_maker(leaves, scale: float, s: int):
    """key -> one silo's packet row (1-D, L symbols), in one program."""
    from repro.core.packets import pytree_to_packet

    shapes = [(tuple(sh), jnp.dtype(dt)) for _, sh, dt in leaves]

    @jax.jit
    def row(key):
        tree = [(scale * jax.random.normal(jax.random.fold_in(key, i), sh,
                                           jnp.float32)).astype(dt)
                for i, (sh, dt) in enumerate(shapes)]
        return pytree_to_packet(tree, s=s)[0]

    return row


def payload(mesh, leaves, K: int, seed: int, scale: float, s: int):
    """The (K, L) payload, lanes split over `mesh`, built row by row."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as Spec

    chips = mesh.devices.size
    rows = NamedSharding(mesh, Spec("data"))
    make = _row_maker(leaves, scale, s)
    root = jax.random.PRNGKey(np.uint32(seed))
    spread = []
    for k in range(K):
        row = make(jax.random.fold_in(root, k))
        if row.shape[0] % chips:
            raise ValueError(f"L = {row.shape[0]} does not split over "
                             f"{chips} chips")
        spread.append(jax.block_until_ready(jax.device_put(row, rows)))
        del row
    stack = jax.jit(lambda *r: jnp.stack(r),
                    out_shardings=NamedSharding(mesh, Spec(None, "data")))
    P = jax.block_until_ready(stack(*spread))
    del spread
    return P


def _program_round(state: State, key):
    """One call of the entry point under test: the decoded packets, or
    None where the draw left no K independent tuples."""
    out = state.engine.round(state.P, key)
    return out.packets if out.ok else None


def _control_round(state: State, key):
    """Encode with K drawn rows, decode with their inverse over GF(2^4);
    None where that inverse does not exist."""
    eng = state.engine
    A = eng.coding_matrix(key, state.K, state.K)
    inv, _ = gf.solve_first_rank(np.asarray(A) & 0xF,
                                 np.eye(state.K, dtype=np.uint8), state.K,
                                 s=4)
    if inv is None:
        return None
    return eng.matmul(jnp.asarray(inv), eng.matmul(A, state.P),
                      stage="decode")


def _round(state: State, key):
    fn = _control_round if state.ctx.control else _program_round
    return jax.block_until_ready(fn(state, key))


def _key(seed: int, attempt: int):
    return jax.random.PRNGKey(np.uint32(u32(seed, 31, attempt)))


def setup(ctx) -> State:
    from jax.sharding import Mesh

    from repro.engine import CodingEngine, EngineConfig

    cfg_, tr = ctx.config, ctx.traffic
    K, s = int(tr["clients"]), int(cfg_["s"])
    mesh = Mesh(np.asarray(ctx.devices), ("data",))
    P = payload(mesh, cfg_["update"]["leaves"], K, u32(ctx.seed, 7),
                float(cfg_["update"]["scale"]), s)
    engine = CodingEngine(EngineConfig(
        s=s, kernel=tr["kernel"], extra_tuples=int(tr["extra_tuples"])),
        mesh=mesh)
    state = State(ctx, engine, P, jax.jit(jnp.array_equal), K,
                  int(P.shape[1]))
    out = _round(state, _key(ctx.seed, 1 << 20))
    if out is not None:
        bool(state.same(out, P))
    return state


def window(state: State, seconds: float) -> dict:
    eng = state.engine
    d0 = eng.dispatch_count
    elapsed, attempts, rounds, wrong = 0.0, 0, 0, 0
    while elapsed < seconds:
        key = _key(state.ctx.seed, attempts)
        with state.ctx.annotate("bench.round"):
            t = clock()
            out = _round(state, key)
            elapsed += clock() - t
        attempts += 1
        if out is not None:
            rounds += 1
            with state.ctx.annotate("bench.check"):
                wrong += not bool(state.same(out, state.P))
        del out
    return {"window_s": elapsed, "attempts": attempts, "rounds": rounds,
            "rounds_wrong": wrong, "dispatches": eng.dispatch_count - d0,
            "K": state.K, "L": state.L, "chips": state.P.sharding.num_devices}


def describe(obs: dict) -> dict:
    return dict(obs)


def check(state: State, obs: dict):
    checks = [_check("rounds_wrong", obs["rounds_wrong"], 0, "<="),
              _check("rounds_checked", obs["rounds"], 1, ">=")]
    return checks, obs["rounds"], obs["rounds_wrong"]
