"""The FedAvg mean in numpy: what a FedNC round must return.

A decoded round aggregates the clients' updates as FedAvg does (paper
§II-A): per leaf, the weighted sum over clients in client order,
accumulated in float32 starting from 0, then cast to the leaf's dtype.
Coding is lossless, so a correct round equals this bit for bit.
"""
from __future__ import annotations

import hashlib
from typing import Callable, Sequence

import numpy as np


def weights(K: int) -> np.ndarray:
    w = np.asarray([1.0 / K] * K, np.float32)
    return w / w.sum()


def mean_leaf(xs: Sequence[np.ndarray], w: np.ndarray,
              acc_dtype=np.float32) -> np.ndarray:
    """Σ_k w_k·x_k in `acc_dtype`, cast back to the leaves' dtype."""
    acc = 0
    for wk, x in zip(w, xs, strict=True):
        acc = acc + acc_dtype(wk) * np.asarray(x).astype(acc_dtype)
    return np.asarray(acc).astype(np.asarray(xs[0]).dtype)


def digest(leaves: Sequence[np.ndarray]) -> str:
    """blake2b over the leaves' raw bytes, in order."""
    h = hashlib.blake2b(digest_size=16)
    for x in leaves:
        x = np.ascontiguousarray(x)
        h.update(str((x.shape, x.dtype.str)).encode())
        h.update(x.view(np.uint8).reshape(-1).data)
    return h.hexdigest()


def mean_digest(n_leaves: int, client_leaf: Callable[[int, int], np.ndarray],
                K: int, acc_dtype=np.float32) -> str:
    """Digest of the FedAvg mean, built one leaf at a time so the host
    holds one leaf of every client at most.  `client_leaf(k, i)` returns
    leaf i of client k as a numpy array."""
    w = weights(K)
    return digest(mean_leaf([client_leaf(k, i) for k in range(K)], w,
                            acc_dtype) for i in range(n_leaves))

