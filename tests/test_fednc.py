"""Algorithm-1 round logic: bit-exact FedNC == FedAvg, skip-on-failure,
channel integration."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import fednc
from repro.core.channel import (BlindBoxChannel, ErasureChannel,
                                MultiHopChannel)
from repro.core.fednc import FedNCConfig


def _clients(n, seed=0):
    key = jax.random.PRNGKey(seed)
    out = []
    for i in range(n):
        k = jax.random.fold_in(key, i)
        out.append({
            "w": jax.random.normal(k, (8, 4), jnp.float32),
            "b": jax.random.normal(jax.random.fold_in(k, 1), (4,)),
        })
    return out


@pytest.mark.parametrize("s", [4, 8])
def test_fednc_equals_fedavg_when_decodable(s):
    """The coding layer is bit-exact (packets are raw float bytes), so
    a successful FedNC round reproduces FedAvg EXACTLY — the paper's
    'no accuracy cost' claim, made literal."""
    clients = _clients(5)
    weights = [0.1, 0.2, 0.3, 0.25, 0.15]
    prev = clients[0]
    cfg = FedNCConfig(s=s, kernel_impl="jnp")
    res_nc = fednc.fednc_round(clients, weights, prev, cfg,
                               jax.random.PRNGKey(42))
    res_avg = fednc.fedavg_round(clients, weights, prev)
    if res_nc.decoded:
        for k in ("w", "b"):
            np.testing.assert_array_equal(
                np.asarray(res_nc.global_params[k]),
                np.asarray(res_avg.global_params[k]))


def _flush(a):
    """Float32 subnormals to signed zero: XLA flushes them, as operands
    and as results, on the CPU as on the TPU (eager ops too)."""
    a = np.asarray(a, np.float32)
    return np.where(np.abs(a) < np.finfo(np.float32).tiny,
                    np.copysign(np.float32(0), a), a).astype(np.float32)


def _numpy_mean(xs, w):
    """FedAvg's mean of one leaf: float32 from 0, client order, cast back."""
    acc = np.float32(0)
    for wk, x in zip(w, xs, strict=True):
        acc = _flush(acc + _flush(wk * _flush(np.asarray(x, np.float32))))
    return acc.astype(np.asarray(xs[0]).dtype)


def _special_clients(K, dtype, seed):
    """K trees of `dtype`: an odd-size leaf, a scalar leaf, and a leaf
    that holds -0.0 in every client, subnormals, +inf in client 0 and
    -inf in the last client."""
    rng = np.random.default_rng(seed)
    tiny = float(jnp.finfo(dtype).smallest_subnormal)
    out = []
    for k in range(K):
        special = rng.normal(size=6).astype(np.float32)
        special[0] = -0.0
        special[1] = tiny * (k + 1)
        special[2] = -tiny * 3
        special[3] = np.inf if k == 0 else special[3]
        special[4] = -np.inf if k == K - 1 else special[4]
        out.append({"odd": jnp.asarray(rng.normal(size=(7, 3)), dtype),
                    "scalar": jnp.asarray(rng.normal(), dtype),
                    "special": jnp.asarray(special).astype(dtype)})
    return out


@pytest.mark.parametrize("s", [8, 4])
@pytest.mark.parametrize("K", [1, 3, 8, 33])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "float16"])
def test_compiled_aggregate_is_bit_exact(dtype, K, s):
    """The compiled aggregate of decoded symbols equals the sequential
    numpy FedAvg sum and `fedavg_round`, byte for byte."""
    clients = _special_clients(K, jnp.dtype(dtype), seed=K * 10 + s)
    weights = list(np.random.default_rng(K).uniform(0.1, 1.0, K))
    P, spec = fednc.engine_for(FedNCConfig(s=s)).packetize(clients)
    agg = fednc.aggregate_decoded(P, spec, weights, FedNCConfig(s=s))
    avg = fednc.fedavg_round(clients, weights, clients[0]).global_params
    w = np.asarray(weights, np.float32)
    w = w / w.sum()
    for name in ("odd", "scalar", "special"):
        ref = _numpy_mean([c[name] for c in clients], w)
        got = np.asarray(agg[name])
        assert got.dtype == ref.dtype and got.shape == ref.shape
        assert got.tobytes() == ref.tobytes(), (name, got, ref)
        assert got.tobytes() == np.asarray(avg[name]).tobytes(), name


def test_compiled_aggregate_builds_no_stacked_copy():
    """The aggregate's scratch memory stays below K float32 copies of
    the tree: no stacked or per-client float32 tree is materialized."""
    K = 8
    tree = {"w": jnp.zeros((64, 32), jnp.float32),
            "b": jnp.zeros((32,), jnp.bfloat16),
            "e": jnp.zeros((100, 16), jnp.float16)}
    P, spec = fednc.engine_for(FedNCConfig(s=8)).packetize([tree] * K)
    compiled = fednc._weighted_mean.lower(
        P, np.full(K, 1 / K, np.float32), np.float32(0), spec).compile()
    f32_bytes = 4 * sum(x.size for x in jax.tree_util.tree_leaves(tree))
    assert compiled.memory_analysis().temp_size_in_bytes < K * f32_bytes


def test_round_skip_keeps_previous_global():
    """Singular coding matrix -> Alg. 1 else-branch: w_t = w_{t-1}."""
    clients = _clients(4)
    prev = {"w": jnp.full((8, 4), 7.0), "b": jnp.zeros((4,))}
    cfg = FedNCConfig(s=1)  # GF(2): singular with high probability
    skipped = 0
    for seed in range(12):
        res = fednc.fednc_round(clients, [0.25] * 4, prev, cfg,
                                jax.random.PRNGKey(seed))
        if not res.decoded:
            skipped += 1
            assert res.global_params is prev
    assert skipped >= 1    # GF(2) 4x4 singular w.p. ~0.69


def test_erasure_channel_failure_path():
    clients = _clients(4)
    prev = clients[0]
    cfg = FedNCConfig(s=8)
    chan = ErasureChannel(p_erase=0.9, seed=0)
    res = fednc.fednc_round(clients, [0.25] * 4, prev, cfg,
                            jax.random.PRNGKey(0), channel=chan)
    if not res.decoded:
        assert res.global_params is prev
        assert res.report is not None


def test_extra_tuples_beat_erasure():
    """FedNC with K+extra coded tuples tolerates erasures that would
    stall FedAvg (robustness §III-A.3)."""
    clients = _clients(4, seed=3)
    prev = clients[0]
    cfg = FedNCConfig(s=8, extra_tuples=4)
    chan = ErasureChannel(p_erase=0.25, seed=5)
    successes = 0
    for seed in range(6):
        res = fednc.fednc_round(clients, [0.25] * 4, prev, cfg,
                                jax.random.PRNGKey(seed), channel=chan)
        successes += int(res.decoded)
    assert successes >= 3


def test_multihop_recode_roundtrip():
    clients = _clients(3, seed=9)
    prev = clients[0]
    cfg = FedNCConfig(s=8)
    chan = MultiHopChannel(eta=4, seed=2)
    res = fednc.fednc_round(clients, [1, 1, 1], prev, cfg,
                            jax.random.PRNGKey(1), channel=chan)
    if res.decoded:
        ref = fednc.fedavg_round(clients, [1, 1, 1], prev)
        np.testing.assert_array_equal(
            np.asarray(res.global_params["w"]),
            np.asarray(ref.global_params["w"]))


def test_strategies_blind_box():
    from repro.federation.server import FedAvgStrategy, FedNCStrategy
    clients = _clients(5, seed=11)
    weights = [0.2] * 5
    prev = clients[0]
    rng = np.random.default_rng(0)
    # FedNC through a blind box with budget=K decodes w.h.p. (s=8) and
    # equals the all-client FedAvg aggregate
    st_nc = FedNCStrategy(config=FedNCConfig(s=8),
                          channel=BlindBoxChannel(budget=5))
    res = st_nc.aggregate(clients, weights, prev, rng)
    if res.decoded:
        ref = fednc.fedavg_round(clients, weights, prev)
        np.testing.assert_array_equal(
            np.asarray(res.global_params["w"]),
            np.asarray(ref.global_params["w"]))
    # FedAvg through the same blind box usually hears < 5 distinct
    st_avg = FedAvgStrategy(channel=BlindBoxChannel(budget=5))
    res2 = st_avg.aggregate(clients, weights, prev,
                            np.random.default_rng(1))
    assert res2.report.distinct_sources <= 5
