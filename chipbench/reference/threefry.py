"""Threefry-2x32-20 and the seed-addressed coding row, in numpy.

FedNC's seeded wire format ships a 4-byte seed per packet: coefficient j
of the row is byte ``j % 4`` of the first Threefry-2x32-20 output word
under key ``(seed, 0x46644E43)`` and counter ``(j // 4, 0)``, masked to
s bits.  This copy is pinned to the Random123 known-answer vectors.
"""
from __future__ import annotations

import numpy as np

SALT = 0x46644E43
_C240 = 0x1BD11BDA
_ROT = (13, 15, 26, 6, 17, 29, 16, 24)


def _rotl(x: np.ndarray, r: int) -> np.ndarray:
    return (x << np.uint32(r)) | (x >> np.uint32(32 - r))


def threefry2x32(k0, k1, x0, x1) -> tuple[np.ndarray, np.ndarray]:
    """The 20-round block function on broadcastable uint32 arrays."""
    k0, k1, x0, x1 = (np.asarray(v, np.uint32) for v in (k0, k1, x0, x1))
    k0, k1, x0, x1 = np.broadcast_arrays(k0, k1, x0, x1)
    ks = (k0, k1, np.uint32(_C240) ^ k0 ^ k1)
    with np.errstate(over="ignore"):            # uint32 adds wrap
        x0 = x0 + ks[0]
        x1 = x1 + ks[1]
        for d in range(20):
            x0 = x0 + x1
            x1 = _rotl(x1, _ROT[d % 8]) ^ x0
            if d % 4 == 3:
                j = d // 4 + 1
                x0 = x0 + ks[j % 3]
                x1 = x1 + ks[(j + 1) % 3] + np.uint32(j)
    return x0, x1


def rows(seeds, K: int, s: int = 8) -> np.ndarray:
    """(N,) uint32 seeds -> (N, K) uint8 coding rows."""
    seeds = np.asarray(seeds, np.uint32).reshape(-1)
    n_words = -(-K // 4)
    ctr = np.arange(n_words, dtype=np.uint32)[None, :]
    w0, _ = threefry2x32(seeds[:, None], np.uint32(SALT), ctr,
                         np.zeros_like(ctr))
    shifts = np.arange(4, dtype=np.uint32) * np.uint32(8)
    b = (w0[:, :, None] >> shifts) & np.uint32(0xFF)
    flat = b.reshape(seeds.shape[0], n_words * 4)[:, :K]
    return (flat & np.uint32((1 << s) - 1)).astype(np.uint8)
