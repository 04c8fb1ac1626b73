"""StreamDecoder: incremental Gaussian elimination over an arrival stream.

The batch decoder (:meth:`CodingEngine.decode`) needs the whole coded
stack in hand before it can start; under a real network the server
hears tuples *one at a time*, and Prop. 1 says it is done the moment
any K linearly-independent ones have arrived — typically the first
~K arrivals.  This module turns that proposition into an executable
state machine:

* The decoder maintains the same reduced-basis state as
  ``engine/select.py:incremental_select`` — ``B`` (K, K) in reduced
  row-echelon form with one row per filled pivot column — extended
  with a payload block ``Y`` (K, L) that receives *identical* row
  operations.  Invariant: for every filled pivot p, ``B[p]·P = Y[p]``.
* ``push(a, c)`` reduces one arrival against the basis in a single GF
  mat-vec (B is RREF, so one pass clears every filled pivot).  A
  nonzero residual is normalized and inserted; a zero residual is a
  *redundant* arrival (linearly dependent — the stream analogue of a
  duplicate blind-box draw) and is dropped.
* When ``rank == K``, B has become the identity, so ``Y`` *is* the
  decoded packet matrix — no final solve.  GF arithmetic is exact,
  hence the result is bit-identical to the batch decode of any
  full-rank subset (property-tested in tests/test_sim.py).
* ``ingest`` consumes a whole block of arrivals as ONE jitted
  ``lax.scan`` dispatch and returns the rank trajectory — the bulk
  path `repro.sim` uses so a round's rank evolution costs one
  dispatch, not one per packet.

States: ``FILLING`` (rank < K) -> ``COMPLETE`` (rank == K; further
pushes are no-ops).  ``decoded_at`` records the 1-based arrival count
at which rank K was reached — the measured Prop.-1 draw count.

The reduced basis doubles as a byzantine tripwire: a *dependent*
arrival whose payload residual is nonzero violates the invariant
B[p]·P = Y[p] and proves corruption somewhere on the stream (see
``reduce_insert``).  Block ingests flag such arrivals for free
(``inconsistent`` / ``first_inconsistent_at``); per-arrival ``push``
keeps checking after COMPLETE only when constructed with
``detect=True`` (the extra dispatches are pure verification).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.gf import get_field
from repro.core.rlnc import EncodedBatch, SeededBatch
from repro.core.seeds import expand_rows

from .select import reduce_insert


@functools.lru_cache(maxsize=None)
def _push_fn(s: int):
    field = get_field(s)

    @jax.jit
    def push(B, Y, filled, a, c):
        B, Y, filled, found, bad = reduce_insert(field, B, Y, filled,
                                                 a, c)
        return B, Y, filled, found, bad

    return push


@functools.lru_cache(maxsize=None)
def _ingest_fn(s: int):
    field = get_field(s)

    @jax.jit
    def ingest(B, Y, filled, A_rows, C_rows):
        def body(carry, ac):
            B, Y, filled = carry
            a, c = ac
            B, Y, filled, _, bad = reduce_insert(field, B, Y, filled,
                                                 a, c)
            return (B, Y, filled), (jnp.sum(filled).astype(jnp.int32),
                                    bad)

        (B, Y, filled), (ranks, bads) = jax.lax.scan(
            body, (B, Y, filled), (A_rows, C_rows))
        return B, Y, filled, ranks, bads

    return ingest


@functools.lru_cache(maxsize=None)
def _ingest_seeded_fn(s: int, K: int):
    """Seed-addressed ingest: rows regenerated inside the scan body.

    Only 4 bytes of coding metadata per arrival ever cross into the
    dispatch — the K-symbol row exists transiently in-register per
    scan step.  `col_mask` zeroes coefficients of absent sources (the
    simulator's dropout columns) before reduction, matching the
    materialized path's ``rows[:, ~live] = 0``."""
    field = get_field(s)

    @jax.jit
    def ingest(B, Y, filled, seeds, C_rows, col_mask):
        def body(carry, sc):
            B, Y, filled = carry
            seed, c = sc
            a = expand_rows(seed[None], K, s)[0]
            a = jnp.where(col_mask, a, jnp.uint8(0))
            B, Y, filled, _, bad = reduce_insert(field, B, Y, filled,
                                                 a, c)
            return (B, Y, filled), (jnp.sum(filled).astype(jnp.int32),
                                    bad)

        (B, Y, filled), (ranks, bads) = jax.lax.scan(
            body, (B, Y, filled), (seeds, C_rows))
        return B, Y, filled, ranks, bads

    return ingest


class StreamDecoder:
    """Consume coded tuples in arrival order; decode at rank K.

    ``L`` is the payload width in symbols (0 = track rank only, e.g.
    for the network simulator's draw counting).

    >>> import jax, jax.numpy as jnp
    >>> from repro.core.gf import get_field
    >>> f = get_field(8)
    >>> P = jnp.arange(12, dtype=jnp.uint8).reshape(3, 4)
    >>> A = f.random_elements(jax.random.PRNGKey(0), (5, 3))
    >>> C = f.matmul(A, P)
    >>> dec = StreamDecoder(K=3, L=4, s=8)
    >>> for g in range(5):                 # arrivals, one at a time
    ...     _ = dec.push(A[g], C[g])
    ...     if dec.complete:
    ...         break
    >>> ok, P_hat = dec.decode()
    >>> bool(ok) and (P_hat == P).all().item(), dec.decoded_at
    (True, 3)
    """

    def __init__(self, K: int, L: int = 0, s: int = 8,
                 detect: bool = False):
        self.K, self.L, self.s = int(K), int(L), int(s)
        self.detect = bool(detect)
        self.field = get_field(s)
        self._B = jnp.zeros((self.K, self.K), jnp.uint8)
        self._Y = jnp.zeros((self.K, self.L), jnp.uint8)
        self._filled = jnp.zeros((self.K,), jnp.bool_)
        self.arrivals = 0          # tuples consumed
        self.decoded_at: Optional[int] = None   # arrival count at rank K
        self.inconsistent = 0      # provably-corrupted arrivals seen
        self.first_inconsistent_at: Optional[int] = None

    # -- state ------------------------------------------------------------

    @property
    def rank(self) -> int:
        return int(jnp.sum(self._filled))

    @property
    def complete(self) -> bool:
        return self.decoded_at is not None

    @property
    def state(self) -> str:
        return "COMPLETE" if self.complete else "FILLING"

    @property
    def tampered(self) -> bool:
        """True once any arrival proved inconsistent with the basis —
        the stream carried at least one corrupted tuple."""
        return self.inconsistent > 0

    # -- consumption ------------------------------------------------------

    def _payload(self, c) -> jnp.ndarray:
        if c is None:
            return jnp.zeros((self.L,), jnp.uint8)
        return jnp.asarray(c, jnp.uint8)

    def push(self, a, c=None) -> int:
        """Consume one arrival (coding vector `a`, payload `c`).

        `a` may be a scalar uint32 *seed* instead of a (K,) row — the
        seed-addressed wire format — in which case the row is
        regenerated here (`repro.core.seeds`).  Returns the rank after
        the arrival.  Pushes after COMPLETE are counted but ignored
        (the server has already decoded) unless ``detect=True``, in
        which case they are still reduced so payload-inconsistent
        redundancy keeps tripping the byzantine counter."""
        self.arrivals += 1
        if self.complete and not self.detect:
            return self.K
        a = jnp.asarray(a)
        if a.dtype == jnp.uint32 and a.ndim == 0:
            a = expand_rows(a[None], self.K, self.s)[0]
        self._B, self._Y, self._filled, _, bad = _push_fn(self.s)(
            self._B, self._Y, self._filled,
            jnp.asarray(a, jnp.uint8), self._payload(c))
        if bool(bad):
            self.inconsistent += 1
            if self.first_inconsistent_at is None:
                self.first_inconsistent_at = self.arrivals
        r = self.rank
        if r == self.K and self.decoded_at is None:
            self.decoded_at = self.arrivals
        return r

    def _record_block(self, g: int, prior: int, already: bool,
                      ranks, bads) -> np.ndarray:
        self.arrivals += g
        ranks = np.asarray(ranks)
        bads = np.asarray(bads)
        if not already and ranks.size and ranks[-1] == self.K:
            self.decoded_at = prior + int(np.argmax(ranks == self.K)) + 1
        if bads.any():
            self.inconsistent += int(bads.sum())
            if self.first_inconsistent_at is None:
                self.first_inconsistent_at = prior + int(
                    np.argmax(bads)) + 1
        return ranks

    def ingest(self, A_rows, C_rows=None) -> np.ndarray:
        """Consume a block of arrivals as one scan dispatch.

        A 1-D uint32 `A_rows` is treated as a block of row *seeds*
        (see :meth:`ingest_seeded`).  Returns the (g,) rank-after-
        each-arrival trajectory; updates ``decoded_at`` with the first
        arrival index reaching K."""
        A_rows = jnp.asarray(A_rows)
        if A_rows.ndim == 1 and A_rows.dtype == jnp.uint32:
            return self.ingest_seeded(A_rows, C_rows)
        A_rows = jnp.asarray(A_rows, jnp.uint8)
        g = A_rows.shape[0]
        if C_rows is None:
            C_rows = jnp.zeros((g, self.L), jnp.uint8)
        prior = self.arrivals
        already = self.complete
        self._B, self._Y, self._filled, ranks, bads = _ingest_fn(self.s)(
            self._B, self._Y, self._filled, A_rows,
            jnp.asarray(C_rows, jnp.uint8))
        return self._record_block(g, prior, already, ranks, bads)

    def ingest_seeded(self, seeds, C_rows=None,
                      col_mask=None) -> np.ndarray:
        """Consume a block of seed-addressed arrivals (one dispatch).

        `seeds` is (g,) uint32; each row is regenerated *inside* the
        jitted scan, so per-arrival coding metadata is 4 bytes instead
        of K symbols.  `col_mask` (K,) bool zeroes the coefficients of
        absent sources before reduction — the simulator's dropout
        semantics, bit-identical to masking the materialized rows."""
        seeds = jnp.asarray(seeds, jnp.uint32)
        g = seeds.shape[0]
        if C_rows is None:
            C_rows = jnp.zeros((g, self.L), jnp.uint8)
        mask = (jnp.ones((self.K,), jnp.bool_) if col_mask is None
                else jnp.asarray(col_mask, jnp.bool_))
        prior = self.arrivals
        already = self.complete
        self._B, self._Y, self._filled, ranks, bads = _ingest_seeded_fn(
            self.s, self.K)(
            self._B, self._Y, self._filled, seeds,
            jnp.asarray(C_rows, jnp.uint8), mask)
        return self._record_block(g, prior, already, ranks, bads)

    # -- the result -------------------------------------------------------

    def decode(self) -> tuple[bool, Optional[jnp.ndarray]]:
        """(ok, P_hat).  At rank K the basis is the identity, so the
        payload block is already the decoded packet matrix."""
        if not self.complete:
            return False, None
        return True, self._Y

    def basis(self) -> jnp.ndarray:
        """The current reduced basis (diagnostics / tests)."""
        return self._B


@functools.lru_cache(maxsize=None)
def _bank_fns(s: int, K: int):
    """The multi-tenant tick kernel: one scan over a padded row block.

    Shared by the batched (vmapped over job slots — ONE dispatch per
    tick regardless of how many jobs are in flight) and the sequential
    (one dispatch per slot; the serving benchmark's baseline) paths, so
    the two differ only in dispatch granularity, never in math.

    Each scan step consumes one wire tuple that may be *either* format:
    ``use_seed`` selects between the materialized (K,) row and the row
    regenerated in-dispatch from the 4-byte seed (`repro.core.seeds` —
    counter-based, so expanding to the bank-wide padded K and masking
    is bit-identical to expanding to the job's own K).  ``valid=False``
    rows (scheduler padding) and masked columns (per-job generation
    size / dropout) are zeroed before reduction — a zero row has zero
    residual, so padding is an exact no-op on [B | Y] and on the rank
    trajectory."""
    field = get_field(s)

    def one(B, Y, filled, rows, seeds, use_seed, valid, C, col_mask):
        def body(carry, x):
            B, Y, filled = carry
            row, seed, use, ok, c = x
            gen = expand_rows(seed[None], K, s)[0]
            a = jnp.where(use, gen, row)
            a = jnp.where(col_mask & ok, a, jnp.uint8(0))
            B, Y, filled, _, _ = reduce_insert(field, B, Y, filled,
                                               a, c)
            return (B, Y, filled), jnp.sum(filled).astype(jnp.int32)

        (B, Y, filled), ranks = jax.lax.scan(
            body, (B, Y, filled), (rows, seeds, use_seed, valid, C))
        return B, Y, filled, ranks

    return jax.jit(jax.vmap(one)), jax.jit(one)


class DecoderBank:
    """J :class:`StreamDecoder` states advanced by one batched dispatch.

    The serving layer (`repro.serve`) holds many federated rounds in
    flight at once; each *slot* of the bank is one job's reduced-basis
    state ``[B | Y]`` (exactly the single-job invariant documented
    above), stacked along a leading jobs axis.  :meth:`ingest` consumes
    a padded ``(slots, g)`` tick block of arrivals for ALL jobs as one
    vmapped `lax.scan` — the continuous-batching analogue of a
    chunked-prefill step, with per-job basis state playing the role of
    per-request prefix state.

    All slots share the bank-wide padded shape (``K`` coefficient
    columns, ``L`` payload symbols); a job with a smaller generation
    size ``k`` simply masks the columns beyond ``k`` (and a shorter
    payload zero-pads — GF row ops never mix columns, so padding
    columns stay zero).  Bit-exactness vs. per-job StreamDecoders is
    property-tested in tests/test_serve.py.

    >>> import jax.numpy as jnp
    >>> bank = DecoderBank(slots=2, K=2, L=4)
    >>> bank.open(0, k=2), bank.open(1, k=2)
    (0, 1)
    >>> P = jnp.arange(8, dtype=jnp.uint8).reshape(2, 4)
    >>> eye = jnp.eye(2, dtype=jnp.uint8)
    >>> ranks = bank.ingest(rows=jnp.stack([eye, eye]),
    ...                     C=jnp.stack([P, P ^ 1]))
    >>> ranks.tolist()                     # both jobs, one dispatch
    [[1, 2], [1, 2]]
    >>> bank.complete.tolist()
    [True, True]
    >>> bool((bank.payload(1) == (P ^ 1)).all())
    True
    """

    def __init__(self, slots: int, K: int, L: int, s: int = 8):
        self.slots, self.K, self.L, self.s = (int(slots), int(K),
                                              int(L), int(s))
        self._B = jnp.zeros((self.slots, self.K, self.K), jnp.uint8)
        self._Y = jnp.zeros((self.slots, self.K, self.L), jnp.uint8)
        self._filled = jnp.zeros((self.slots, self.K), jnp.bool_)
        self._col_mask = np.zeros((self.slots, self.K), bool)
        self._k = np.zeros((self.slots,), np.int64)   # 0 = slot closed
        self._l = np.zeros((self.slots,), np.int64)
        self.dispatches = 0

    # -- slot lifecycle ---------------------------------------------------

    def open(self, slot: int, k: int, l: Optional[int] = None,
             col_mask=None) -> int:
        """(Re)initialize `slot` for a job with generation size `k`.

        `col_mask` (k,) bool masks dropped sources (the StreamDecoder
        ``col_mask`` semantics); columns beyond `k` are always masked.
        Returns the slot index."""
        slot = int(slot)
        if not 0 < k <= self.K:
            raise ValueError(f"job k={k} exceeds bank K={self.K}")
        l = self.L if l is None else int(l)
        if l > self.L:
            raise ValueError(f"job L={l} exceeds bank L={self.L}")
        self._B = self._B.at[slot].set(jnp.uint8(0))
        self._Y = self._Y.at[slot].set(jnp.uint8(0))
        self._filled = self._filled.at[slot].set(False)
        mask = np.zeros((self.K,), bool)
        mask[:k] = True if col_mask is None else np.asarray(col_mask,
                                                            bool)[:k]
        self._col_mask[slot] = mask
        self._k[slot] = k
        self._l[slot] = l
        return slot

    def close(self, slot: int) -> None:
        """Retire a slot (its state stays until the next `open`)."""
        self._k[int(slot)] = 0

    @property
    def open_slots(self) -> np.ndarray:
        return np.nonzero(self._k > 0)[0]

    @property
    def target(self) -> np.ndarray:
        """(slots,) per-job target rank (0 for closed slots)."""
        return self._k.copy()

    @property
    def rank(self) -> np.ndarray:
        return np.asarray(jnp.sum(self._filled, axis=1))

    @property
    def complete(self) -> np.ndarray:
        """(slots,) — open slots whose basis reached their target rank."""
        return (self._k > 0) & (self.rank >= self._k)

    # -- the tick ---------------------------------------------------------

    def _tick_args(self, rows, seeds, use_seed, valid, C):
        g = None
        for arr in (rows, seeds, C):
            if arr is not None:
                g = int(jnp.asarray(arr).shape[1])
                break
        if g is None:
            raise ValueError("need rows, seeds, or C to size the tick")
        J, K, L = self.slots, self.K, self.L
        rows = (jnp.zeros((J, g, K), jnp.uint8) if rows is None
                else jnp.asarray(rows, jnp.uint8))
        seeds = (jnp.zeros((J, g), jnp.uint32) if seeds is None
                 else jnp.asarray(seeds, jnp.uint32))
        use_seed = (jnp.zeros((J, g), jnp.bool_) if use_seed is None
                    else jnp.asarray(use_seed, jnp.bool_))
        valid = (jnp.ones((J, g), jnp.bool_) if valid is None
                 else jnp.asarray(valid, jnp.bool_))
        C = (jnp.zeros((J, g, L), jnp.uint8) if C is None
             else jnp.asarray(C, jnp.uint8))
        return rows, seeds, use_seed, valid, C

    def ingest(self, rows=None, seeds=None, use_seed=None, valid=None,
               C=None, *, batched: bool = True) -> jax.Array | np.ndarray:
        """Advance every slot by one padded (slots, g) tick block.

        `rows` (slots, g, K) uint8 materialized coding rows, `seeds`
        (slots, g) uint32 row seeds, `use_seed` (slots, g) bool format
        selector per tuple, `valid` (slots, g) bool padding mask, `C`
        (slots, g, L) uint8 payloads; omitted arrays default to zeros
        (and `valid` to all-true).  Returns the (slots, g) rank-after-
        each-arrival trajectory: on the batched path a device array
        that the caller reads when it needs it (the read waits for the
        scan), so the dispatch itself never blocks.

        ``batched=True`` advances all slots in ONE vmapped dispatch;
        ``batched=False`` runs the identical per-slot kernel once per
        slot holding work — the sequential per-job baseline the serving
        benchmark measures against.  Both paths are bit-identical.
        """
        rows, seeds, use_seed, valid, C = self._tick_args(
            rows, seeds, use_seed, valid, C)
        mask = jnp.asarray(self._col_mask)
        batched_fn, single_fn = _bank_fns(self.s, self.K)
        if batched:
            self._B, self._Y, self._filled, ranks = batched_fn(
                self._B, self._Y, self._filled, rows, seeds, use_seed,
                valid, C, mask)
            self.dispatches += 1
            return ranks
        ranks = np.zeros(valid.shape, np.int32)
        work = np.asarray(jnp.any(valid, axis=1))
        base = self.rank
        for j in range(self.slots):
            if not work[j]:
                ranks[j] = base[j]
                continue
            Bj, Yj, fj, rj = single_fn(
                self._B[j], self._Y[j], self._filled[j], rows[j],
                seeds[j], use_seed[j], valid[j], C[j], mask[j])
            self._B = self._B.at[j].set(Bj)
            self._Y = self._Y.at[j].set(Yj)
            self._filled = self._filled.at[j].set(fj)
            self.dispatches += 1
            ranks[j] = np.asarray(rj)
        return ranks

    # -- results ----------------------------------------------------------

    def payload(self, slot: int) -> jnp.ndarray:
        """The decoded (k, l) packet matrix of a complete slot.

        At rank k the basis restricted to the job's columns is the
        identity, so rows [0, k) of Y are the decoded packets."""
        slot = int(slot)
        k, l = int(self._k[slot]), int(self._l[slot])
        return self._Y[slot, :k, :l]

    def basis(self, slot: int) -> jnp.ndarray:
        return self._B[int(slot)]


def stream_decode(batch, s: int, order=None
                  ) -> tuple[bool, Optional[jnp.ndarray], int]:
    """Decode an EncodedBatch (or SeededBatch) row-by-row in arrival order.

    `order` permutes the rows (default: transmission order).  Returns
    ``(ok, P_hat, consumed)`` where `consumed` is the number of
    arrivals the server actually needed — the rank-K prefix length
    (`decoded_at`; n when rank K was never reached).

    The whole batch goes through one `ingest` scan dispatch: arrivals
    past the rank-K prefix reduce to zero against the completed basis
    and are no-ops, so the decode is identical to stopping at the
    prefix while avoiding a dispatch + host sync per arrival.  A
    :class:`SeededBatch` flows through the seed-addressed scan — its
    rows are regenerated in-dispatch and the decode is bit-identical
    to streaming the expanded batch.
    """
    K = batch.K
    rows = batch.seeds if isinstance(batch, SeededBatch) else batch.A
    dec = StreamDecoder(K=K, L=batch.C.shape[1], s=s)
    if order is None:
        dec.ingest(rows, batch.C)
    else:
        idx = jnp.asarray(np.asarray(order), jnp.int32)
        dec.ingest(rows[idx], batch.C[idx])
    ok, P_hat = dec.decode()
    return bool(ok), P_hat, (dec.decoded_at if dec.complete
                             else dec.arrivals)
