"""Partial updates through FedNC's normal path: each client uploads the
whole leaves and only the expert blocks it trained; the round codes one
generation per block among its holders and must equal the per-block
FedAvg mean bit for bit (``chipbench/reference/fedavg_blocks.py``)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import fedavg, fedavg_blocks
from repro import obs
from repro.core import packets as pkt
from repro.core.channel import ErasureChannel
from repro.core.fednc import FedNCConfig, engine_for, fednc_round
from repro.engine import CodingEngine, EngineConfig
from repro.engine.select import incremental_select, select_invert_padded

K = 4
GRID = (2, 4)                      # 2 MoE layers x 4 held experts (of 16)
D, FF = 64, 16
# holders per block: 0 nobody, 1 everybody, 2 a single silo
HELD = [(1, 2, 5), (1, 3, 5, 6), (1, 5), (1, 3, 6)]
WEIGHTS = [1.0, 2.0, 3.0, 4.0]
SHAPES = {"a": ((64,), jnp.bfloat16), "b": ((3, 5), jnp.float32)}
EXPERT = {"w_gate": (D, FF), "w_down": (FF, D)}


def _holders():
    return [tuple(k for k in range(K) if b in HELD[k])
            for b in range(int(np.prod(GRID)))]


def _clients(seed=0, held=HELD):
    rng = np.random.default_rng(seed)

    def arr(shape, dtype):
        return jnp.asarray(rng.normal(size=shape) * 0.02, dtype)

    out = []
    for k in range(K):
        tree = {n: arr(*sd) for n, sd in SHAPES.items()}
        tree["moe"] = {n: pkt.Blocks(arr((len(held[k]), *sh), jnp.bfloat16),
                                     held[k], GRID)
                       for n, sh in EXPERT.items()}
        out.append(tree)
    return out


def _prev(seed=1):
    rng = np.random.default_rng(seed)
    tree = {n: jnp.asarray(rng.normal(size=sh), dt)
            for n, (sh, dt) in SHAPES.items()}
    tree["moe"] = {n: jnp.asarray(rng.normal(size=GRID + sh), jnp.bfloat16)
                   for n, sh in EXPERT.items()}
    return tree


def _reference(clients, prev, absent_as_zero=False):
    """Per-block FedAvg mean, leaf by leaf, in the trees' leaf order."""
    holders = _holders()
    out = {}
    for n in SHAPES:
        out[n] = fedavg.mean_leaf([np.asarray(c[n]) for c in clients],
                                  fedavg_blocks.block_weights(WEIGHTS,
                                                              range(K)))
    out["moe"] = {}
    for n in EXPERT:
        def block(k, b, n=n):
            x = clients[k]["moe"][n]
            return np.asarray(x.rows[x.ids.index(b)])
        if not absent_as_zero:
            out["moe"][n] = fedavg_blocks.mean_blocked_leaf(
                np.asarray(prev["moe"][n]), GRID, holders, block, WEIGHTS)
            continue
        leaf = np.array(prev["moe"][n])
        zero = np.zeros(EXPERT[n], leaf.dtype)
        for b, hs in enumerate(holders):
            leaf[np.unravel_index(b, GRID)] = fedavg.mean_leaf(
                [block(k, b) if k in hs else zero for k in range(K)],
                fedavg_blocks.block_weights(WEIGHTS, range(K)))
        out["moe"][n] = leaf
    return out


def _equal(a, b) -> bool:
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        np.asarray(x).dtype == np.asarray(y).dtype
        and np.array_equal(np.asarray(x).view(np.uint8),
                           np.asarray(y).view(np.uint8))
        for x, y in zip(la, lb, strict=True))


def _round(kernel, channel, chunk_l=1024, seed=0, key=0):
    cfg = FedNCConfig(kernel_impl=kernel, extra_tuples=2, chunk_l=chunk_l)
    clients, prev = _clients(seed), _prev()
    for t in range(20):        # retry an undecodable attempt, as a server
        ch = ErasureChannel(0.2, seed=100 + t) if channel else None
        res = fednc_round(clients, WEIGHTS, prev, cfg,
                          jax.random.PRNGKey(key + t), channel=ch)
        if res.decoded:
            return res, clients, prev
    raise AssertionError("no attempt decoded")


@pytest.mark.parametrize("s", [8, 4])
def test_leafwise_packetize_is_the_batched_one(s):
    rng = np.random.default_rng(s)
    trees = [{"a": jnp.asarray(rng.normal(size=(5, 3)), jnp.bfloat16),
              "b": jnp.asarray(rng.normal(size=(7,)), jnp.float32),
              "c": jnp.asarray(rng.integers(0, 256, (2, 2)), jnp.uint8),
              "d": jnp.float32(rng.normal())} for _ in range(3)]
    P, spec = pkt.pytrees_to_packets(trees, s)
    rows, specs = zip(*(pkt.pytree_to_packet(t, s) for t in trees))
    assert all(sp == spec for sp in specs)
    assert np.array_equal(np.asarray(P), np.asarray(pkt.stack_packets(
        list(rows))))


def test_the_layout_has_every_kind_of_block():
    assert [len(h) for h in _holders()] == [0, 4, 1, 2, 0, 3, 2, 0]


@pytest.mark.parametrize("channel", [False, True], ids=["ideal", "erasure"])
@pytest.mark.parametrize("kernel", ["jnp", "jnp_packed", "jnp_packed_seeded",
                                    "pallas_packed"])
def test_block_round_is_the_per_block_fedavg_mean(kernel, channel):
    res, clients, prev = _round(kernel, channel)
    assert res.decoded and res.n_aggregated == K
    assert _equal(res.global_params, _reference(clients, prev))


def test_absent_as_zero_control_differs():
    res, clients, prev = _round("jnp", True)
    assert not _equal(res.global_params,
                      _reference(clients, prev, absent_as_zero=True))


def test_block_nobody_holds_keeps_prev():
    res, _, prev = _round("jnp", False)
    for n in EXPERT:
        for b in (0, 4, 7):
            at = np.unravel_index(b, GRID)
            assert np.array_equal(np.asarray(res.global_params["moe"][n][at]),
                                  np.asarray(prev["moe"][n][at]))


@pytest.mark.parametrize("channel", [False, True], ids=["ideal", "erasure"])
def test_all_blocks_round_is_todays_round(channel):
    """Every client holding every block is the single-generation round:
    same aggregate bit for bit, same dispatch count."""
    everything = [tuple(range(int(np.prod(GRID))))] * K
    blocked = _clients(3, held=everything)
    whole = [{**c, "moe": {n: b.rows.reshape(GRID + b.rows.shape[1:])
                           for n, b in c["moe"].items()}} for c in blocked]
    cfg = FedNCConfig(kernel_impl="jnp_packed", extra_tuples=2, chunk_l=1024)
    eng = engine_for(cfg)
    outs = []
    for trees in (whole, blocked):
        d0 = eng.dispatch_count
        ch = ErasureChannel(0.2, seed=5) if channel else None
        res = fednc_round(trees, WEIGHTS, _prev(), cfg,
                          jax.random.PRNGKey(9), channel=ch)
        outs.append((res.decoded, res.global_params, eng.dispatch_count - d0))
    assert outs[0][0] and outs[1][0]
    assert _equal(outs[0][1], outs[1][1])
    assert outs[0][2] == outs[1][2] > 0


def test_round_records_generations_and_spans():
    _round("jnp", False, chunk_l=0)
    rec = obs.last_records("fednc.round", 1)[0]
    holders = [h for h in _holders() if h]
    L_d = (64 * 2 + 15 * 4)
    L_b = 2 * D * FF * 2
    assert rec.counts["engine.generations"] == 1 + len(holders)
    assert rec.counts["engine.coded_symbols"] == \
        K * L_d + sum(len(h) for h in holders) * L_b
    assert {"fednc.packetize", "fednc.blocks", "engine.solve",
            "fednc.aggregate"} <= set(rec.spans)


@pytest.mark.parametrize("L", [4096, 4000], ids=["whole_chunks", "tail"])
def test_generations_decode_in_place_chunk_by_chunk(L):
    eng = CodingEngine(EngineConfig(kernel="jnp_packed", chunk_l=1024,
                                    extra_tuples=1))
    rng = np.random.default_rng(L)
    Ps = [rng.integers(0, 256, (k, L), dtype=np.uint8) for k in (5, 1, 3)]
    d0 = eng.dispatch_count
    out = eng.round([jnp.asarray(p) for p in Ps], jax.random.PRNGKey(0))
    assert out.ok
    assert all(np.array_equal(np.asarray(a), p)
               for a, p in zip(out.packets, Ps, strict=True))
    assert eng.dispatch_count - d0 == 2 * 3 * 4


def test_a_generation_short_of_rank_fails_the_attempt():
    class DropAllOf:
        """Erasure plan that delivers nothing of the second generation."""
        def __init__(self):
            self.g = 0

        def plan_transform(self, n, s):
            from repro.core.channel import RowGather
            self.g += 1
            return RowGather(np.arange(0 if self.g == 2 else n))

    eng = CodingEngine(EngineConfig(kernel="jnp", extra_tuples=1))
    Ps = [jnp.ones((2, 8), jnp.uint8), jnp.ones((1, 8), jnp.uint8)]
    out = eng.round(Ps, jax.random.PRNGKey(0), channel=DropAllOf())
    assert not out.ok and out.packets is None
    assert out.report.sent == 5 and out.report.delivered == 3


@pytest.mark.parametrize("seed", range(4))
def test_padded_select_matches_incremental_select(seed):
    rng = np.random.default_rng(seed)
    k, n, km, nm = 3, 5, 6, 8
    A = rng.integers(0, 256, (n, k), dtype=np.uint8)
    A[1] = A[0]                      # a dependent row to skip
    padded = np.zeros((nm, km), np.uint8)
    padded[:n, :k] = A
    ok, sel, A_inv = select_invert_padded(jnp.asarray(padded), k, 8)
    ok0, sel0, _ = incremental_select(jnp.asarray(A), 8)
    assert bool(ok) == bool(ok0)
    assert np.asarray(sel)[:k].tolist() == np.asarray(sel0).tolist()
    assert np.array_equal(np.asarray(A_inv)[k:, k:], np.eye(km - k))
    from repro.core.gf import get_field
    eye = get_field(8).matmul(A_inv[:k, :k], jnp.asarray(A)[sel[:k]])
    assert np.array_equal(np.asarray(eye), np.eye(k))


def test_clients_holding_different_blocks_of_one_grid_are_refused():
    clients = _clients()
    m = clients[0]["moe"]
    m["w_down"] = pkt.Blocks(m["w_down"].rows[:2], m["w_down"].ids[:2], GRID)
    with pytest.raises(ValueError, match="different blocks"):
        pkt.split_blocks(clients)
