"""The generator gives every seed the same work in another order."""
import numpy as np
import pytest

from chipbench import arrivals, harness

TRAFFIC = harness.Layout().traffic("serve.gen16.open")


def test_same_sizes_and_delays_on_every_seed():
    a = arrivals.open_loop(TRAFFIC, 1, 45.0)
    b = arrivals.open_loop(TRAFFIC, 2**31 + 99, 45.0)
    assert a.gens == b.gens == int(45 * TRAFFIC["rate_gen_per_s"])
    assert a.starts[-1] == pytest.approx(b.starts[-1]) == pytest.approx(45.0)
    def gaps(ol):
        t = np.concatenate([ol.starts[:, None], ol.due], axis=1)
        return np.sort(np.diff(t, axis=1).ravel())
    np.testing.assert_allclose(gaps(a), gaps(b))
    assert not np.array_equal(a.seeds, b.seeds)
    assert a.seeded.sum() == b.seeded.sum() == (a.gens + 1) // 2


def test_every_generation_keeps_enough_distinct_packets():
    ol = arrivals.open_loop(TRAFFIC, 7, 45.0)
    dup = sum(int((row[1:] == row[:-1]).sum()) for row in ol.seeds)
    assert dup == round(TRAFFIC["duplicate_rate"] * ol.gens * ol.n)
    distinct = np.array([len(set(row.tolist())) for row in ol.seeds])
    assert distinct.min() >= ol.n - 2 > ol.k
    assert np.all(np.diff(ol.due.reshape(-1)[ol.order]) >= 0)
