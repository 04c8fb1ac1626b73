"""Plain GF(2^s) arithmetic in numpy: log/antilog tables, matmul, solve.

The benchmark's own field, independent of the program under test.  The
fields are the ones FedNC states: GF(2^8) modulo x^8+x^4+x^3+x^2+1
(0x11D) and, for the control, GF(2^4) modulo x^4+x+1 (0x13).
"""
from __future__ import annotations

import numpy as np

POLY = {4: 0x13, 8: 0x11D}


def tables(s: int) -> tuple[np.ndarray, np.ndarray]:
    """(exp, log): exp has 2·(2^s − 1) entries so exp[log a + log b]
    needs no reduction; log[0] is unused."""
    q = 1 << s
    exp = np.zeros(2 * (q - 1), np.int64)
    log = np.zeros(q, np.int64)
    x = 1
    for i in range(q - 1):
        exp[i] = x
        log[x] = i
        x <<= 1
        if x & q:
            x ^= POLY[s]
    exp[q - 1:] = exp[:q - 1]
    return exp, log


def mul_table(s: int) -> np.ndarray:
    """(2^s, 2^s) uint8 product table."""
    exp, log = tables(s)
    q = 1 << s
    a = np.arange(q)[:, None]
    b = np.arange(q)[None, :]
    t = exp[(log[a] + log[b]) % (q - 1)]
    return np.where((a == 0) | (b == 0), 0, t).astype(np.uint8)


def inverse(a: int, s: int) -> int:
    exp, log = tables(s)
    return int(exp[(-log[a]) % ((1 << s) - 1)])


def matmul(A: np.ndarray, B: np.ndarray, s: int = 8) -> np.ndarray:
    """C = A·B over GF(2^s); A (n, k), B (k, l) uint8."""
    A = np.asarray(A, np.uint8)
    B = np.asarray(B, np.uint8)
    mul = mul_table(s)
    C = np.zeros((A.shape[0], B.shape[1]), np.uint8)
    for j in range(A.shape[1]):
        C ^= mul[A[:, j][:, None], B[j][None, :]]
    return C


def solve_first_rank(rows: np.ndarray, payloads: np.ndarray, k: int,
                     s: int = 8):
    """Gaussian elimination over arrivals in order, stopping at rank k.

    Returns (P, used): the (k, l) solution and the number of arrivals
    consumed, or (None, len(rows)) if the rows never reach rank k.
    """
    mul = mul_table(s)
    basis: dict[int, tuple[np.ndarray, np.ndarray]] = {}
    for used, (a, c) in enumerate(zip(rows, payloads), start=1):
        a = np.asarray(a, np.uint8).copy()
        c = np.asarray(c, np.uint8).copy()
        for p, (ba, bc) in basis.items():
            if a[p]:
                f = a[p]
                a ^= mul[f, ba]
                c ^= mul[f, bc]
        nz = np.nonzero(a)[0]
        if nz.size == 0:
            continue
        p = int(nz[0])
        inv = inverse(int(a[p]), s)
        a = mul[inv, a]
        c = mul[inv, c]
        for q, (ba, bc) in list(basis.items()):
            if ba[p]:
                f = ba[p]
                basis[q] = (ba ^ mul[f, a], bc ^ mul[f, c])
        basis[p] = (a, c)
        if len(basis) == k:
            return np.stack([basis[i][1] for i in range(k)]), used
    return None, len(rows)
