"""jit-safe incremental-GE row selection for the n > K erasure path.

The seed's `select_decodable_rows` was a host-side numpy greedy loop
that recomputed the rank of the picked prefix from scratch for every
candidate row — O(n·K) full eliminations, with a device->host sync per
row.  This module replaces it with a single forward elimination pass
that maintains pivot state on-device:

* ``B`` (K, K): the reduced basis — row c holds the (normalized) basis
  vector whose pivot sits in column c, zero if that pivot is unfilled.
  ``B`` is kept in *reduced* row-echelon form, so reducing a candidate
  row against the whole basis is one GF mat-vec.
* A candidate row is selected iff its reduction against the basis is
  nonzero (i.e. it is independent of everything selected so far) —
  exactly the greedy matroid rule of the old helper, so the selected
  index set is identical.

Everything is `lax.fori_loop` + `lax.cond`: no host numpy, no sync,
usable inside jit and under vmap over batches of coding matrices.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.gf import get_field, invert


def reduce_insert(field, B, Y, filled, a, c):
    """One candidate row (a, c) against the RREF basis [B | Y].

    The shared elimination step of `incremental_select` (Y zero-width)
    and `engine.stream.StreamDecoder` (Y = the payload block): reduce
    `a` in a single GF mat-vec (B is RREF, so subtracting a[p]·B[p]
    for every filled pivot p zeroes all filled pivot columns at once),
    and — when the residual is nonzero, i.e. the row is independent —
    normalize by the residual's first nonzero symbol and insert at
    that pivot, clearing its column from the existing rows to stay
    RREF.  Identical row operations hit Y, preserving the invariant
    B[p]·P = Y[p].  Returns ``(B, Y, filled, was_independent,
    inconsistent)``.

    ``inconsistent`` is the byzantine tripwire: an honest dependent
    arrival (a, c) = (Σ λ_p B[p], Σ λ_p Y[p]) reduces to zero in BOTH
    the coefficient and the payload column, so a zero coefficient
    residual with a NONZERO payload residual proves some tuple on this
    stream was corrupted (flipped symbols, a forged coding row, or a
    replayed seed with a different payload) — no honest channel, lossy
    or recoding, can produce it.
    """
    coeffs = jnp.where(filled, a, jnp.uint8(0))
    red_a = a ^ field.matmul(coeffs[None, :], B)[0]
    red_c = c ^ field.matmul(coeffs[None, :], Y)[0]
    nz = red_a != 0
    found = jnp.any(nz)
    bad = (~found) & jnp.any(red_c != 0)
    piv = jnp.argmax(nz)                    # first nonzero column

    def insert(args):
        B, Y, filled = args
        inv = field.inv(red_a[piv])
        new_a = field.mul(red_a, inv)
        new_c = field.mul(red_c, inv)
        fac = B[:, piv]
        B = (B ^ field.mul(fac[:, None], new_a[None, :])).at[piv].set(new_a)
        Y = (Y ^ field.mul(fac[:, None], new_c[None, :])).at[piv].set(new_c)
        return B, Y, filled.at[piv].set(True)

    B, Y, filled = jax.lax.cond(found, insert, lambda args: args,
                                (B, Y, filled))
    return B, Y, filled, found, bad


@functools.lru_cache(maxsize=None)
def _select_fn(s: int):
    field = get_field(s)

    @jax.jit
    def run(A: jnp.ndarray):
        A = jnp.asarray(A, jnp.uint8)
        n, K = A.shape
        c0 = jnp.zeros((0,), jnp.uint8)     # selection carries no payload

        def body(i, state):
            B, Y, filled, sel, count = state
            B, Y, filled, found, _ = reduce_insert(field, B, Y, filled,
                                                   A[i], c0)
            sel = jnp.where(found, sel.at[count].set(i), sel)
            return B, Y, filled, sel, count + found.astype(jnp.int32)

        state = (
            jnp.zeros((K, K), jnp.uint8),       # basis B
            jnp.zeros((K, 0), jnp.uint8),       # zero-width payload
            jnp.zeros((K,), jnp.bool_),         # filled pivots
            jnp.zeros((K,), jnp.int32),         # selected row indices
            jnp.int32(0),                       # selected count
        )
        _, _, _, sel, count = jax.lax.fori_loop(0, n, body, state)
        return count == K, sel, count

    return run


def incremental_select(A: jnp.ndarray, s: int):
    """Greedily pick K independent rows of A (n, K) over GF(2^s).

    Returns ``(ok, idx, count)``: `ok` — scalar bool, full column rank
    reached; `idx` — (K,) int32 selected row indices in scan order
    (positions >= count are 0-padded, matching the old helper); `count`
    — number of independent rows found (== rank of A, capped at K).

    Row 1 below is 2·row 0 over GF(2^8), so the selector skips it:

    >>> import jax.numpy as jnp
    >>> A = jnp.array([[1, 0], [2, 0], [0, 3]], dtype=jnp.uint8)
    >>> ok, idx, count = incremental_select(A, 8)
    >>> bool(ok), idx.tolist(), int(count)
    (True, [0, 2], 2)
    """
    return _select_fn(s)(A)


def select_invert_padded(A: jnp.ndarray, k, s: int):
    """Select and invert one generation padded to (n, K_max).

    Rows of A past the generation's delivered ones, and its columns
    ``>= k``, are zero.  The masked columns start as filled pivots with
    identity basis rows, so the elimination (:func:`reduce_insert`)
    picks k independent rows among the real ones, exactly as
    :func:`incremental_select` does on the unpadded (n, k) matrix; the
    padded selection ``[[A_sel, 0], [0, I]]`` then inverts to
    ``[[A_sel^-1, 0], [0, I]]``.  Traced (jit- and vmap-safe, `k` may be
    traced): the engine maps it over a round's generations.

    Returns ``(ok, idx, A_inv)``: `idx` (K_max,) the selected rows in
    scan order (positions ``>= k`` are 0), `A_inv` (K_max, K_max).
    """
    field = get_field(s)
    n, km = A.shape
    masked = jnp.arange(km) >= k
    eye = jnp.eye(km, dtype=jnp.uint8)
    c0 = jnp.zeros((0,), jnp.uint8)

    def body(i, state):
        B, Y, filled, sel, count = state
        B, Y, filled, found, _ = reduce_insert(field, B, Y, filled, A[i], c0)
        sel = jnp.where(found, sel.at[count].set(i), sel)
        return B, Y, filled, sel, count + found.astype(jnp.int32)

    state = (jnp.where(masked[:, None], eye, jnp.uint8(0)),
             jnp.zeros((km, 0), jnp.uint8), masked,
             jnp.zeros((km,), jnp.int32), jnp.int32(0))
    _, _, _, sel, count = jax.lax.fori_loop(0, n, body, state)
    square = jnp.where((jnp.arange(km) < k)[:, None], A[sel], eye)
    ok_inv, A_inv = invert(field, square)
    return (count == k) & ok_inv, sel, A_inv
