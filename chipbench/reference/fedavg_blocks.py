"""The per-block FedAvg mean in numpy: what an expert-selective round must
return.

Each silo uploads the leaves every silo holds whole and, of the blocked
leaves (routed experts), only the blocks it trained.  The mean of a
whole leaf runs over all K silos, with weights w_k / Σ w; block b of a
blocked leaf runs over the silos holding it, S_b, with weights
w_k / Σ_{j ∈ S_b} w_j; weights in float32, each sum accumulated in
float32 from 0 in client order, then cast to the leaf's dtype.  A block
no silo holds keeps the previous global model's value.  Coding is
lossless, so a correct round equals this bit for bit.
"""
from __future__ import annotations

from typing import Callable, Sequence

import numpy as np

from chipbench.reference import fedavg


def block_weights(w: Sequence[float], holders: Sequence[int]) -> np.ndarray:
    """The float32 weights of the silos `holders`, renormalized over them."""
    ws = np.asarray(w, np.float32)[list(holders)]
    return ws / ws.sum()


def mean_blocked_leaf(prev: np.ndarray, grid: tuple,
                      holders: Sequence[Sequence[int]],
                      block: Callable[[int, int], np.ndarray],
                      w: Sequence[float],
                      acc_dtype=np.float32) -> np.ndarray:
    """A blocked leaf: `prev` (its whole previous value, leading axes
    `grid`) with block b replaced by the mean of ``block(k, b)`` over
    ``holders[b]``."""
    out = np.array(prev, copy=True)
    for b, hs in enumerate(holders):
        if hs:
            out[np.unravel_index(b, grid)] = fedavg.mean_leaf(
                [block(k, b) for k in hs], block_weights(w, hs), acc_dtype)
    return out


def mean_digest(n_leaves: int, blocked: dict, w: Sequence[float],
                whole: Callable[[int, int], np.ndarray],
                prev: Callable[[int], np.ndarray],
                block: Callable[[int, int, int], np.ndarray],
                acc_dtype=np.float32) -> str:
    """Digest of the per-block FedAvg mean, built one leaf at a time so
    the host holds one leaf of every silo at most.

    `blocked` maps a blocked leaf's position to ``(grid, holders)``;
    ``whole(k, i)`` is leaf i of silo k, ``prev(i)`` the previous
    global value of a blocked leaf, ``block(k, i, b)`` block b of leaf i
    as silo k holds it."""
    K = len(w)

    def leaf(i):
        if i not in blocked:
            return fedavg.mean_leaf([whole(k, i) for k in range(K)],
                                    block_weights(w, range(K)), acc_dtype)
        grid, holders = blocked[i]
        return mean_blocked_leaf(prev(i), grid, holders,
                                 lambda k, b: block(k, i, b), w, acc_dtype)

    return fedavg.digest(leaf(i) for i in range(n_leaves))
