"""The benchmark's reference copies agree with the program's own oracles
at tiny sizes: table GF, Threefry rows, the FedAvg mean."""
import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

from chipbench.reference import fedavg, gf, threefry

# Random123 known-answer vectors for Threefry-2x32-20
KAT = [((0x00000000, 0x00000000, 0x00000000, 0x00000000),
        (0x6B200159, 0x99BA4EFE)),
       ((0xFFFFFFFF, 0xFFFFFFFF, 0xFFFFFFFF, 0xFFFFFFFF),
        (0x1CB996FC, 0xBB002BE7)),
       ((0x13198A2E, 0x03707344, 0x243F6A88, 0x85A308D3),
        (0xC4923A9C, 0x483DF7A0))]


@pytest.mark.parametrize("kat", KAT, ids=["zeros", "ones", "pi"])
def test_threefry_known_answers(kat):
    (k0, k1, x0, x1), want = kat
    got = threefry.threefry2x32(k0, k1, x0, x1)
    assert (int(got[0]), int(got[1])) == want


@pytest.mark.parametrize("K", [1, 3, 16, 37])
def test_rows_match_the_programs_seed_expansion(K):
    from repro.core.seeds import expand_rows
    seeds = np.array([0, 1, 7, 0xDEADBEEF, 0xFFFFFFFF], np.uint32)
    for s in (4, 8):
        want = np.asarray(expand_rows(jnp.asarray(seeds), K, s))
        np.testing.assert_array_equal(threefry.rows(seeds, K, s), want)


@pytest.mark.parametrize("s", [4, 8])
def test_table_gf_matches_the_programs_field(s):
    from repro.core.gf import get_field
    rng = np.random.default_rng(s)
    A = rng.integers(0, 1 << s, (5, 7), dtype=np.uint8)
    B = rng.integers(0, 1 << s, (7, 64), dtype=np.uint8)
    want = np.asarray(get_field(s).matmul(jnp.asarray(A), jnp.asarray(B)))
    np.testing.assert_array_equal(gf.matmul(A, B, s), want)


def test_solve_first_rank_recovers_the_source():
    rng = np.random.default_rng(0)
    P = rng.integers(0, 256, (4, 32), dtype=np.uint8)
    A = rng.integers(0, 256, (7, 4), dtype=np.uint8)
    A[2] = A[1]                                        # a duplicate
    got, used = gf.solve_first_rank(A, gf.matmul(A, P), 4)
    np.testing.assert_array_equal(got, P)
    assert 4 <= used <= 7


def test_fedavg_mean_equals_the_programs_fedavg_round():
    from repro.core.fednc import fedavg_round
    K = 5
    clients = [[jax.random.normal(jax.random.PRNGKey(k), (3, 33),
                                  jnp.bfloat16),
                jax.random.normal(jax.random.PRNGKey(100 + k), (17,))]
               for k in range(K)]
    want = fedavg_round(clients, [1.0 / K] * K, clients[0]).global_params
    w = fedavg.weights(K)
    for i in range(2):
        got = fedavg.mean_leaf([np.asarray(c[i]) for c in clients], w)
        np.testing.assert_array_equal(got.view(np.uint8),
                                      np.asarray(want[i]).view(np.uint8))
    ref = fedavg.mean_digest(2, lambda k, i: np.asarray(clients[k][i]), K)
    assert ref == fedavg.digest([np.asarray(x) for x in want])
    low = fedavg.mean_digest(2, lambda k, i: np.asarray(clients[k][i]), K,
                             acc_dtype=ml_dtypes.bfloat16)
    assert low != ref
