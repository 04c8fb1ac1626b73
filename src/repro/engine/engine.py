"""CodingEngine: the unified encode -> channel -> select -> decode spine.

FedNC's entire round cost is the coded matmul C = A·P and its GE
inverse (paper §II-B, Alg. 1).  The seed scattered that hot path over
four layers with host-side Python in the middle; this engine owns it
end to end as one jit-first, chunked, multi-device program:

* **batched packetization** — client pytrees are stacked once and
  byte/symbol-split under `vmap` (core.packets.pytrees_to_packets); no
  per-client Python loop.
* **registry dispatch** — the kernel is a name resolved through
  repro.engine.registry (`EngineConfig.kernel`), replacing the
  `impl="auto"|"jnp"|"pallas"` strings that used to live in three
  places.
* **chunked streaming executor** — the lane dimension L is tiled into
  fixed `chunk_l`-symbol blocks.  Each block is dispatched
  asynchronously, so models larger than VMEM stream through the Pallas
  kernel, and in `round()` the decode of chunk i overlaps the encode
  of chunk i+1 (no cross-chunk data dependency is ever introduced).
* **jit-safe selection** — the n > K erasure path picks K independent
  rows with the incremental-GE pass in repro.engine.select, entirely
  on-device.
* **multi-device lanes** — given a mesh (launch.mesh), the kernel is
  wrapped in `shard_map` sharding L across the configured axis; lanes
  are embarrassingly parallel, so there is no communication.
* **fused channels and recoding** — channels that expose their action
  on the row space (`plan_transform` -> RowGather/RowMix) are folded
  into the stream: the erasure pattern / composed relay mix is decided
  on the tiny (n, K) coding matrix first, then encode, channel, and
  decode run as ONE chunk-streamed dispatch.  `recode()` is the
  network-interior relay operation (Prop. 2), and `multi_edge_round()`
  runs the whole hierarchical topology (paper §III) as a single fused
  dispatch in the global coding-vector space.

`core.fednc.fednc_round`, the federation strategies, and
`core.hierarchy` are thin adapters over this class.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import packets as pkt
from repro.core import seeds as seedlib
from repro.core.channel import ChannelReport, RowGather, RowMix, RowTamper
from repro.core.gf import get_field, invert
from repro.core.rlnc import EncodedBatch, SeededBatch

from .defaults import DEFAULT_CHUNK_L
from .registry import (is_seeded_kernel, materialized_kernel_name,
                       resolve_kernel, seeded_kernel_name)
from .select import incremental_select, select_invert_padded


def _is_seed_rows(A) -> bool:
    """True iff the row operand is a (n,) uint32 seed vector.

    The two wire formats are structurally disjoint — materialized rows
    are a 2-D uint8 matrix, seed vectors are 1-D uint32 — so dispatch
    is unambiguous."""
    arr = jnp.asarray(A)
    return arr.ndim == 1 and arr.dtype == jnp.uint32


def _on_host(x, read=bool):
    """``read(x)`` of a device value: the round blocks here until the
    device has computed `x`.  Each such wait is a ``fednc.sync`` span
    and counts in ``fednc.syncs``."""
    with obs.span("fednc.sync", cat="engine"):
        obs.count("fednc.syncs")
        return read(x)


def _chunk_plan(lanes: int, width: int, wrap=None):
    """Cut `lanes` payload lanes into `width`-lane chunks.

    Returns ``(count, take, join)``: ``take(X, c)`` is chunk c of X,
    the last one zero-padded to `width` so that every chunk reuses one
    compiled kernel, and ``join(*outs)`` concatenates the chunk outputs
    and trims that padding, so the payload is never copied whole.
    `wrap(fn, indexed)` wraps each piece (the engine maps them over a
    mesh; `indexed` marks the one taking a chunk index).
    """
    count = -(-lanes // width)
    tail = lanes - (count - 1) * width
    wrap = wrap or (lambda fn, indexed: fn)
    cut = wrap(lambda X, c: jax.lax.dynamic_slice_in_dim(
        X, c * width, width, axis=1), True)
    cut_tail = wrap(lambda X: jnp.pad(
        X[:, (count - 1) * width:], ((0, 0), (0, width - tail))), False)
    join = wrap(lambda *outs: jnp.concatenate(
        [*outs[:-1], outs[-1][:, :tail]], axis=1), False)

    def take(X, c: int):
        return cut(X, c) if c < count - 1 or tail == width else cut_tail(X)

    return count, take, join


@functools.lru_cache(maxsize=None)
def _put_chunk(width: int):
    """Write the first `width` lanes of a chunk's output back into the
    payload at lane `start`, in place: the payload is donated, so a
    stream decoded chunk by chunk never holds a second payload."""
    return jax.jit(lambda X, Y, start: jax.lax.dynamic_update_slice_in_dim(
        X, Y[:, :width], start, axis=1), donate_argnums=0)


@dataclass(frozen=True)
class EngineConfig:
    """Everything the coding spine needs, in one hashable record."""

    s: int = 8                   # field size (symbol bits), paper Table I
    kernel: str = "auto"         # registry name (see engine.registry)
    chunk_l: int = DEFAULT_CHUNK_L   # symbols per streamed chunk; 0 = off
    lane_axis: Optional[str] = "data"  # mesh axis sharding L (if meshed)
    extra_tuples: int = 0        # send K + extra coded tuples
    systematic: bool = False     # identity-prefixed coding matrix
    coding_density: float = 1.0  # <1.0 = sparse RLNC coefficients


@dataclass(frozen=True)
class EngineRound:
    """Outcome of one engine round (the coded math, pre-aggregation)."""

    ok: bool
    # (K, L) decoded symbols when ok; a list of them, one per generation,
    # for a round over several generations
    packets: Any
    report: Any = None               # ChannelReport when a channel ran
    # redundant-rank cross-check (round(verify=True)): True = every
    # redundant delivered tuple is consistent with the decode, False =
    # corruption detected, None = not checked / no redundancy to check
    verified: Optional[bool] = None


#: shared default so signatures avoid calls in argument defaults
#: (ruff B008) and `get_engine()` == `get_engine(EngineConfig())` in
#: the lru_cache
_DEFAULT_CONFIG = EngineConfig()


class CodingEngine:
    """Owns the full RLNC pipeline for one EngineConfig (+ optional mesh)."""

    def __init__(self, config: EngineConfig = _DEFAULT_CONFIG,
                 mesh: Any = None):
        self.config = config
        self.mesh = mesh
        self.kernel_name, self._kernel = resolve_kernel(config.kernel)
        # A seeded kernel only covers the *encode* side (rows derived
        # from seeds); decode/recode mix with arbitrary materialized
        # matrices (A^-1, R), which run through the kernel's
        # materialized sibling.  Both siblings are always resolved so
        # any engine can consume either packet format; `self.seeded`
        # governs which format round()/encode() *produce*.
        self.seeded = is_seeded_kernel(self.kernel_name)
        if self.seeded:
            self._seed_kernel = self._kernel
            _, self._mat_kernel = resolve_kernel(
                materialized_kernel_name(self.kernel_name))
        else:
            _, self._seed_kernel = resolve_kernel(
                seeded_kernel_name(self.kernel_name))
            self._mat_kernel = self._kernel
        self.field = get_field(config.s)
        self._dispatch: dict = {}   # built lazily, once
        # per-engine metrics; engine.dispatches counts L-sized kernel
        # dispatches (monotonic; benchmarks diff it around a round)
        self.metrics = obs.MetricsRegistry()
        self._dispatches = self.metrics.counter("engine.dispatches")

    @property
    def dispatch_count(self) -> int:
        """L-sized kernel dispatches issued so far (monotonic)."""
        return self._dispatches.value

    # -- packetization ----------------------------------------------------

    def packetize(self, client_params: Sequence[Any]
                  ) -> tuple[jnp.ndarray, pkt.PacketSpec]:
        """K client pytrees -> (K, L) symbol matrix, vmap-batched."""
        return pkt.pytrees_to_packets(client_params, s=self.config.s)

    def unpacketize(self, P_hat: jnp.ndarray, spec: pkt.PacketSpec):
        """(K, L) decoded symbols -> stacked pytree (leading K axis)."""
        return pkt.packets_to_pytrees(P_hat, spec)

    # -- coding matrices --------------------------------------------------

    def coding_matrix(self, key, n: int, K: int) -> jnp.ndarray:
        from repro.core import rlnc
        cfg = self.config
        if cfg.systematic:
            return rlnc.systematic_coding_matrix(key, n, K, cfg.s)
        if cfg.coding_density < 1.0:
            return rlnc.sparse_coding_matrix(key, n, K, cfg.s,
                                             density=cfg.coding_density)
        return rlnc.random_coding_matrix(key, n, K, cfg.s)

    def coding_seeds(self, key, n: int) -> jnp.ndarray:
        """n uint32 row seeds — the seed-addressed coding "matrix".

        Only the plain uniform RLNC draw has a seeded representation;
        systematic / sparse rows cannot be derived from a 4-byte seed.
        """
        cfg = self.config
        if cfg.systematic or cfg.coding_density < 1.0:
            raise ValueError(
                "seeded coding vectors require plain uniform RLNC "
                "(systematic=False, coding_density=1.0)")
        return seedlib.draw_seeds(key, n)

    def expand_seeds(self, seeds, K: int) -> jnp.ndarray:
        """Materialize the (n, K) rows a seed vector addresses.

        The decode/oracle-side bridge: row-space work (selection,
        inversion, recoding) happens on this tiny matrix while the
        L-sized payload products stay seed-addressed."""
        return seedlib.expand_rows_jit(seeds, K, self.config.s)

    # -- chunked / sharded executor ---------------------------------------

    def _mesh_kernel(self, seeded: bool = False):
        """The registry kernel, shard_map-wrapped over the lane axis.

        Built (and jitted) once per engine (separately for the seeded
        encode kernel and the materialized mixing kernel), so repeat
        chunks dispatch from the compile cache instead of re-tracing
        the shard_map."""
        if seeded in self._dispatch:
            return self._dispatch[seeded]
        kern = self._seed_kernel if seeded else self._mat_kernel
        mesh, axis = self.mesh, self.config.lane_axis
        if mesh is None or axis is None or axis not in mesh.axis_names \
                or mesh.shape[axis] == 1:
            self._dispatch[seeded] = (kern, 1)
            return self._dispatch[seeded]
        from repro.launch.sharding import coded_spec, replicated_spec
        size = int(mesh.shape[axis])
        s = self.config.s
        # the row operand replicates either way: a tiny (n, K) matrix,
        # or the even tinier (n,) seed vector
        row_spec = replicated_spec(1 if seeded else 2)
        sharded = jax.jit(jax.shard_map(
            lambda a, p: kern(a, p, s=s), mesh=mesh,
            in_specs=(row_spec, coded_spec(2, mesh, axis=axis)),
            out_specs=coded_spec(2, mesh, axis=axis),
            check_vma=False,
        ))
        self._dispatch[seeded] = (sharded, size)
        return self._dispatch[seeded]

    def _chunks(self, L: int) -> tuple[int, int]:
        """(chunk width, count) covering L after padding."""
        cl = self.config.chunk_l
        if cl <= 0 or L <= cl:
            return max(L, 1), 1
        return cl, -(-L // cl)

    def _lane_chunking(self, local: int, width: int):
        """(place, count, take, join) for a lane-sharded payload.

        `place` lays a (rows, shards·local) payload over the mesh, each
        device holding its contiguous `local` lanes.  Each device then
        cuts chunk c from its own lanes (:func:`_chunk_plan`), so no
        chunk ever leaves the device that holds it, and `join`
        concatenates each device's chunk outputs in place, which
        restores the payload's lane order and sharding.  Built once per
        (local, width).
        """
        key = ("lanes", local, width)
        if key not in self._dispatch:
            from jax.sharding import NamedSharding
            from repro.launch.sharding import coded_spec, replicated_spec
            mesh = self.mesh
            lane = coded_spec(2, mesh, axis=self.config.lane_axis)

            def on_mesh(fn, indexed: bool):
                specs = (lane, replicated_spec(0)) if indexed else lane
                return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=specs,
                                             out_specs=lane))

            place = functools.partial(jax.device_put,
                                      device=NamedSharding(mesh, lane))
            self._dispatch[key] = (place,
                                   *_chunk_plan(local, width, on_mesh))
        return self._dispatch[key]

    def matmul(self, A: jnp.ndarray, P: jnp.ndarray, *,
               stage: str = "encode") -> jnp.ndarray:
        """C = A·P, chunk-streamed through the configured kernel.

        Chunks are dispatched eagerly (JAX async dispatch), so chunk
        i+1 is enqueued while chunk i still executes on-device.  On a
        seeded engine, pass the (n,) uint32 seed vector as `A` to run
        the seeded encode kernel (rows regenerated in-kernel).
        `stage` names the stream's host span (``engine.<stage>``).
        """
        return self._stream(A, P, enc_seeded=_is_seed_rows(A),
                            stage=stage)

    def _stream(self, A, P, A_post=None, *, enc_seeded: bool = False,
                stage: str = "encode", in_place: bool = False):
        """Run the kernel chunk-by-chunk over the lane dim of P.

        With `A_post` (the decode mixing matrix), each chunk is pushed
        through *both* matmuls before the next chunk is dispatched:
        C_i = A·P_i then A_post·C_i.  No cross-chunk dependency exists,
        so the decode of chunk i overlaps the encode of chunk i+1 via
        async dispatch.  Returns A·P, or A_post·A·P when given.

        With ``enc_seeded`` the first operand is the (n,) uint32 seed
        vector and the encode matmul runs through the seeded kernel —
        coefficient rows are regenerated inside the kernel per chunk,
        so the coding matrix never rides along with the payload.  The
        `A_post` mixing (decode) product always uses the materialized
        kernel.

        With ``in_place`` (a square `A_post`, one device) each decoded
        chunk is written back over the chunk of P it came from, which
        consumes P: the stream then holds one payload, not P, the chunk
        outputs and their join.

        One host span, ``engine.<stage>``, covers slicing, dispatching
        and joining every chunk; it never waits on the device.
        """
        with obs.span(f"engine.{stage}", cat="engine"):
            enc_kernel, shards = self._mesh_kernel(enc_seeded)
            post_kernel, _ = self._mesh_kernel(False)
            s = self.config.s
            if A_post is not None:
                n_out = A_post.shape[0]
            else:
                n_out = A.shape[0]
            L = P.shape[1]
            if L == 0:
                return jnp.zeros((n_out, 0), jnp.uint8)

            def mm(kernel, M, X):
                self._dispatches.inc()
                return kernel(M, X, s=s) if shards == 1 else kernel(M, X)

            cl, nc = self._chunks(L)
            if nc == 1 and L % shards == 0:
                out = mm(enc_kernel, A, P)
                return mm(post_kernel, A_post, out) \
                    if A_post is not None else out
            if shards > 1:
                Lp = L + (-L) % shards
                place, nc, take, join = self._lane_chunking(
                    Lp // shards, -(-cl // shards))
                P = place(jnp.pad(P, ((0, 0), (0, Lp - L))) if Lp != L else P)
            else:
                nc, take, join = _chunk_plan(L, cl)
            in_place = in_place and shards == 1 and A_post is not None \
                and n_out == P.shape[0]
            outs = []
            for c in range(nc):
                enc = mm(enc_kernel, A, take(P, c))
                out = mm(post_kernel, A_post, enc) \
                    if A_post is not None else enc
                if in_place:
                    P = _put_chunk(min(cl, L - c * cl))(P, out, c * cl)
                else:
                    outs.append(out)
            if in_place:
                return P
            out = join(*outs)
            return out[:, :L] if out.shape[1] != L else out

    # -- pipeline stages --------------------------------------------------

    def encode(self, P: jnp.ndarray, A: jnp.ndarray):
        """C = A·P as an EncodedBatch (chunk-streamed).

        P is the (K, L) packet matrix (K clients, L symbols each), A an
        (n, K) coding matrix over GF(2^s) — usually from
        :meth:`coding_matrix`.  Passing a (n,) uint32 seed vector (from
        :meth:`coding_seeds`) instead runs the seeded kernel and
        returns a :class:`SeededBatch` — 4-byte headers on the wire,
        rows regenerated in-kernel.

        >>> import jax, jax.numpy as jnp
        >>> eng = CodingEngine(EngineConfig(s=8, kernel="jnp"))
        >>> P = jnp.arange(12, dtype=jnp.uint8).reshape(3, 4)
        >>> A = eng.coding_matrix(jax.random.PRNGKey(0), n=3, K=3)
        >>> batch = eng.encode(P, A)
        >>> batch.A.shape, batch.C.shape
        ((3, 3), (3, 4))
        """
        if _is_seed_rows(A):
            return self.encode_seeded(P, A)
        return EncodedBatch(A=jnp.asarray(A, jnp.uint8),
                            C=self.matmul(A, P))

    def encode_seeded(self, P: jnp.ndarray, seeds: jnp.ndarray
                      ) -> SeededBatch:
        """C = rows(seeds)·P without materializing the coding matrix.

        Bit-exact vs. ``encode(P, expand_seeds(seeds, K)).C`` — same
        Threefry stream, evaluated inside the kernel per chunk.

        >>> import jax, jax.numpy as jnp
        >>> eng = CodingEngine(EngineConfig(s=8, kernel="jnp"))
        >>> P = jnp.arange(12, dtype=jnp.uint8).reshape(3, 4)
        >>> seeds = eng.coding_seeds(jax.random.PRNGKey(0), n=3)
        >>> sb = eng.encode_seeded(P, seeds)
        >>> mat = eng.encode(P, eng.expand_seeds(seeds, 3))
        >>> (sb.C == mat.C).all().item()
        True
        """
        seeds = jnp.asarray(seeds, jnp.uint32)
        C = self._stream(seeds, P, enc_seeded=True)
        return SeededBatch(seeds=seeds, C=C, K=int(P.shape[0]))

    def recode(self, batch, key, n_out: int) -> EncodedBatch:
        """Relay recoding (paper Prop. 2): emit `n_out` fresh random
        combinations of the received tuples without decoding.

        The relay draws R (n_out, n) over GF(2^s) and forwards
        (R·A, R·C); coding vectors compose linearly, so downstream
        decoders treat the result exactly like first-hop tuples.  Both
        products run through the registry kernel, chunk-streamed
        (`recode_with` for a caller-supplied R).  A :class:`SeededBatch`
        input is accepted — seed-expansion of the tiny (n, K) rows
        happens at the relay, and the output rows are *materialized*:
        a composed row R·A is not derivable from any 4-byte seed, so
        Prop. 2 semantics survive while only first-hop traffic enjoys
        the seeded header.

        >>> import jax, jax.numpy as jnp
        >>> eng = CodingEngine(EngineConfig(s=8, kernel="jnp"))
        >>> P = jnp.arange(12, dtype=jnp.uint8).reshape(3, 4)
        >>> batch = eng.encode(P, eng.coding_matrix(jax.random.PRNGKey(0), 3, 3))
        >>> relay = eng.recode(batch, jax.random.PRNGKey(1), n_out=4)
        >>> relay.A.shape, relay.C.shape          # 4 fresh combinations
        ((4, 3), (4, 4))
        >>> ok, P_hat = eng.decode(relay)         # still decodes to P
        >>> bool(ok) and (P_hat == P).all().item()
        True
        """
        R = self.field.random_elements(key, (n_out, batch.n))
        return self.recode_with(R, batch)

    def recode_with(self, R: jnp.ndarray, batch) -> EncodedBatch:
        """Recode with an explicit mixing matrix: (R·A, R·C).

        η sequential hops compose by linearity — recoding with
        R_η···R_1 (one call) is bit-identical to η calls in sequence;
        `core.channel.MultiHopChannel` relies on exactly that."""
        R = jnp.asarray(R, jnp.uint8)
        if isinstance(batch, SeededBatch):
            batch = batch.expand(self.config.s)
        return EncodedBatch(A=self.matmul(R, batch.A, stage="recode"),
                            C=self.matmul(R, batch.C, stage="recode"))

    def select(self, batch) -> tuple[jnp.ndarray, EncodedBatch]:
        """Pick K independent tuples out of n >= K, fully on-device."""
        if isinstance(batch, SeededBatch):
            batch = batch.expand(self.config.s)
        with obs.span("engine.select", cat="engine", n=int(batch.n)):
            ok, idx, _ = incremental_select(batch.A, self.config.s)
        return ok, EncodedBatch(A=batch.A[idx], C=batch.C[idx])

    def decode(self, batch) -> tuple[bool, Optional[jnp.ndarray]]:
        """(ok, P_hat): select (if n > K), invert A, stream A^-1·C.

        GF arithmetic is exact, so inverting the (tiny) K x K coding
        matrix and streaming A^-1·C chunk-wise is bit-identical to the
        seed's monolithic Gaussian elimination over [A | C].

        >>> import jax, jax.numpy as jnp
        >>> eng = CodingEngine(EngineConfig(s=8, kernel="jnp"))
        >>> P = jnp.arange(12, dtype=jnp.uint8).reshape(3, 4)
        >>> batch = eng.encode(P, eng.coding_matrix(jax.random.PRNGKey(0), 5, 3))
        >>> ok, P_hat = eng.decode(batch[jnp.array([0, 2, 4])])  # 2 erased
        >>> bool(ok) and (P_hat == P).all().item()
        True

        A :class:`SeededBatch` is accepted directly: the receiver
        regenerates the tiny (n, K) coding matrix from the 4-byte
        headers (the L-sized payload never carried the rows)."""
        if isinstance(batch, SeededBatch):
            batch = batch.expand(self.config.s)
        K = batch.K
        if batch.n < K:
            return False, None
        ok = jnp.bool_(True)
        if batch.n > K:
            ok, batch = self.select(batch)
        with obs.span("engine.invert", cat="engine", K=K):
            ok_inv, A_inv = invert(self.field, batch.A)
        if not _on_host(ok & ok_inv):
            return False, None
        return True, self.matmul(A_inv, batch.C, stage="decode")

    def decode_verified(self, batch) -> tuple[bool, Optional[jnp.ndarray],
                                              Optional[bool]]:
        """(ok, P_hat, verified): decode plus the byzantine cross-check.

        Decoding consumes only K of the n delivered tuples; the n - K
        *redundant* ones are free integrity checks: re-encode P_hat
        with each redundant coding row and compare the payload digest
        against what the channel delivered.  Any mismatch proves some
        tuple was corrupted — an honest channel (lossy, reordering, or
        recoding) delivers only exact GF combinations, so every
        redundant row of an uncorrupted stream reproduces its payload
        bit-for-bit.

        ``verified`` is True when every redundant tuple checks out,
        False on any mismatch, and None when there is no redundancy to
        check (n == K after selection) — corruption can then slip
        through undetected, which is why the byzantine benchmarks run
        with ``extra_tuples > 0``.  Note False flags the *round*, not a
        row: a forged row may itself decode cleanly and instead poison
        the check of an honest redundant row; either way the server
        knows to discard and re-request.

        >>> import jax, jax.numpy as jnp
        >>> eng = CodingEngine(EngineConfig(s=8, kernel="jnp"))
        >>> P = jnp.arange(12, dtype=jnp.uint8).reshape(3, 4)
        >>> batch = eng.encode(P, eng.coding_matrix(jax.random.PRNGKey(0), 5, 3))
        >>> ok, P_hat, verified = eng.decode_verified(batch)
        >>> bool(ok), (P_hat == P).all().item(), verified
        (True, True, True)
        >>> bad = EncodedBatch(A=batch.A, C=batch.C.at[4, 0].set(batch.C[4, 0] ^ 1))
        >>> eng.decode_verified(bad)[2]
        False
        """
        import hashlib

        if isinstance(batch, SeededBatch):
            batch = batch.expand(self.config.s)
        K, n = batch.K, batch.n
        if n < K:
            return False, None, None
        ok, idx, _ = incremental_select(batch.A, self.config.s)
        ok_inv, A_inv = invert(self.field, batch.A[idx])
        if not _on_host(ok & ok_inv):
            return False, None, None
        P_hat = self.matmul(A_inv, batch.C[idx], stage="decode")
        red = np.setdiff1d(np.arange(n), _on_host(idx, np.asarray))
        if red.size == 0:
            return True, P_hat, None
        red_j = jnp.asarray(red, jnp.int32)
        pred = self.matmul(batch.A[red_j], P_hat, stage="verify")
        pred_np = _on_host(pred, np.asarray)
        got_np = _on_host(batch.C[red_j], np.asarray)
        verified = all(
            hashlib.sha256(pred_np[i].tobytes()).digest()
            == hashlib.sha256(got_np[i].tobytes()).digest()
            for i in range(red.size))
        return True, P_hat, bool(verified)

    # -- fused round internals --------------------------------------------

    def _fused_ideal_round(self, P: jnp.ndarray, A: jnp.ndarray,
                           seeds: Optional[jnp.ndarray] = None
                           ) -> EngineRound:
        """Lossless-delivery tail: resolve invertibility on the tiny
        (n, K) problem, then stream A_inv·(A_sel·P) in one dispatch.

        When `seeds` is given, A is its expansion; row-space planning
        (selection, inversion) runs on A while the L-sized encode
        product runs the seeded kernel on the matching seed subset."""
        n, K = A.shape
        if n < K:
            return EngineRound(False, None, None)
        ok = jnp.bool_(True)
        if n > K:
            with obs.span("engine.select", cat="engine", n=n):
                ok, idx, _ = incremental_select(A, self.config.s)
            A_sel = A[idx]
            enc = seeds[idx] if seeds is not None else A_sel
        else:
            A_sel = A
            enc = seeds if seeds is not None else A
        with obs.span("engine.invert", cat="engine", K=K):
            ok_inv, A_inv = invert(self.field, A_sel)
        if not _on_host(ok & ok_inv):
            return EngineRound(False, None, None)
        # encode only the selected rows — the ideal channel delivers
        # everything, so unselected erasure-headroom rows are dead work
        # and A_inv·(A_sel·P) is the exact decode.
        P_hat = self._stream(enc, P, A_post=A_inv,
                             enc_seeded=seeds is not None, stage="decode")
        return EngineRound(True, P_hat, None)

    def _expand_err(self, err_seeds, which, width: int) -> jnp.ndarray:
        """Materialize adversarial error rows `which` of a RowTamper
        seed vector at `width` symbols (K for coding rows, L for
        payloads) — same Threefry expansion as the wire format."""
        sel = jnp.asarray(np.asarray(err_seeds)[which], jnp.uint32)
        return seedlib.expand_rows_jit(sel, width, self.config.s)

    def _fused_tamper_round(self, P: jnp.ndarray, A: jnp.ndarray,
                            plan: RowTamper,
                            seeds: Optional[jnp.ndarray] = None,
                            verify: bool = False) -> EngineRound:
        """RowTamper tail: byzantine corruption folded into the stream.

        All n tuples are delivered, rows `plan.idx` XOR-ed with
        seed-expanded noise.  Selection and inversion run on the
        *received* (corrupted) matrix — the server cannot tell a forged
        row from an honest one — while the encode leg replays the true
        rows, so the decode output is exactly what a stage-wise
        receiver of the corrupted batch would compute:

            P_hat = A_rx[sel]^-1 · C_rx[sel]
                  = A_inv·(A_true[sel]·P)  ^  A_inv·E[sel]

        with E the (sparse) payload-error matrix; only its few nonzero
        rows are ever expanded to L symbols.  With `verify`, the
        redundant delivered rows are cross-checked against P_hat
        (:meth:`decode_verified` semantics, residual form) at the cost
        of two extra (n-K)-row streamed products.
        """
        n, K = A.shape
        L = P.shape[1]
        idx_np = np.asarray(plan.idx, np.int64)
        with obs.span("engine.transform", cat="engine", n=n):
            A_rx = A
            if plan.m and plan.row_seeds is not None:
                idx_t = jnp.asarray(idx_np, jnp.int32)
                A_err = self._expand_err(plan.row_seeds,
                                         np.arange(plan.m), K)
                A_rx = A.at[idx_t].set(A[idx_t] ^ A_err)
        with obs.span("engine.select", cat="engine", n=n):
            ok, sel, _ = incremental_select(A_rx, self.config.s)
        ok = _on_host(ok)
        report = ChannelReport(n, n, ok)
        if not ok:
            return EngineRound(False, None, report)
        with obs.span("engine.invert", cat="engine", K=K):
            _, A_inv = invert(self.field, A_rx[sel])
        sel_np = _on_host(sel, np.asarray).astype(np.int64)
        enc_rows = seeds if seeds is not None else A
        P_hat = self._stream(enc_rows[sel], P, A_post=A_inv,
                             enc_seeded=seeds is not None, stage="decode")
        pos_of = {int(r): j for j, r in enumerate(idx_np)}

        def err_at(rows: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
            hit = [(j, pos_of[int(r)]) for j, r in enumerate(rows)
                   if int(r) in pos_of]
            return (np.asarray([h[0] for h in hit], np.int64),
                    np.asarray([h[1] for h in hit], np.int64))

        if plan.payload_seeds is not None and L:
            where, which = err_at(sel_np)
            if where.size:
                E = self._expand_err(plan.payload_seeds, which, L)
                P_hat = P_hat ^ self.field.matmul(
                    A_inv[:, jnp.asarray(where, jnp.int32)], E)
        verified = None
        if verify:
            red = np.setdiff1d(np.arange(n), sel_np)
            if red.size:
                red_j = jnp.asarray(red, jnp.int32)
                C_red = self._stream(enc_rows[red_j], P,
                                     enc_seeded=seeds is not None,
                                     stage="verify")
                if plan.payload_seeds is not None and L:
                    where, which = err_at(red)
                    if where.size:
                        E = self._expand_err(plan.payload_seeds, which, L)
                        w = jnp.asarray(where, jnp.int32)
                        C_red = C_red.at[w].set(C_red[w] ^ E)
                resid = self._stream(A_rx[red_j], P_hat,
                                     stage="verify") ^ C_red
                verified = not _on_host(jnp.any(resid != 0))
        return EngineRound(True, P_hat, report, verified)

    def _fused_channel_round(self, P: jnp.ndarray, A: jnp.ndarray,
                             channel,
                             seeds: Optional[jnp.ndarray] = None,
                             verify: bool = False) -> EngineRound:
        """encode -> channel -> select -> decode as ONE streamed dispatch.

        The channel's `plan_transform` yields its whole action on the
        row space (RowGather erasure pattern / RowMix relay matrix), so
        delivery, selection, and inversion are all resolved on (n, K)-
        sized matrices first.  The L-sized payload then flows through a
        single `_stream` whose A_post composes channel and decode:
        channel simulation overlaps the decode of every chunk, and the
        full coded payload is never materialized between stages.  GF
        algebra is exact and associative, so the result is bit-identical
        to the stage-wise reference.
        """
        n, K = A.shape
        s = self.config.s
        plan = channel.plan_transform(n, s)
        if isinstance(plan, RowTamper):
            # byzantine corruption: the whole round (including the
            # redundant-rank cross-check) has its own fused tail
            return self._fused_tamper_round(P, A, plan, seeds, verify)
        with obs.span("engine.transform", cat="engine", n=n):
            if isinstance(plan, RowGather):
                delivered = int(len(plan.idx))
                if delivered < K:
                    return EngineRound(
                        False, None, ChannelReport(n, delivered, False))
                idx = jnp.asarray(plan.idx, jnp.int32)
                A_rx = A[idx]
            elif isinstance(plan, RowMix):
                delivered = int(plan.R.shape[0])
                A_rx = self.field.matmul(plan.R, A)
            else:
                raise TypeError(
                    f"unsupported channel plan {type(plan).__name__}")
        with obs.span("engine.select", cat="engine", n=delivered):
            ok, sel, _ = incremental_select(A_rx, s)
        ok = _on_host(ok)
        report = ChannelReport(n, delivered, ok)
        if not ok:
            return EngineRound(False, None, report)
        with obs.span("engine.invert", cat="engine", K=K):
            _, A_inv = invert(self.field, A_rx[sel])  # sel independent
        if isinstance(plan, RowGather):
            A_enc, A_post = A[idx[sel]], A_inv
            if seeds is not None:
                A_enc = seeds[idx[sel]]
        else:
            # RowMix touches every source row, so the full seed vector
            # feeds the encode; the relay composition R folds into the
            # materialized A_post (composed rows have no seed).
            A_enc, A_post = A, self.field.matmul(A_inv, plan.R[sel])
            if seeds is not None:
                A_enc = seeds
        P_hat = self._stream(A_enc, P, A_post=A_post,
                             enc_seeded=seeds is not None, stage="decode")
        return EngineRound(True, P_hat, report)

    def _stagewise_channel_round(self, P: jnp.ndarray, A: jnp.ndarray,
                                 channel,
                                 verify: bool = False) -> EngineRound:
        """Fallback for channels without `plan_transform`: materialize
        the coded payload and run the stages in order."""
        batch = self.encode(P, A)
        batch, report = channel.transmit_encoded(batch, self.config.s)
        if not report.decodable:
            return EngineRound(False, None, report)
        if verify:
            ok, P_hat, verified = self.decode_verified(batch)
            return EngineRound(bool(ok), P_hat, report, verified)
        ok, P_hat = self.decode(batch)
        return EngineRound(bool(ok), P_hat, report)

    def _run_round(self, P: jnp.ndarray, A: jnp.ndarray, channel,
                   seeds: Optional[jnp.ndarray] = None,
                   verify: bool = False) -> EngineRound:
        """Shared channel-dispatch tail of `round`/`multi_edge_round`.

        `seeds`, when given, is the seed vector whose expansion is `A`;
        the fused paths then run their encode leg through the seeded
        kernel.  The stage-wise fallback materializes (it already has
        A), which is bit-identical by construction.  `verify` requests
        the redundant-rank cross-check (honored by the stage-wise and
        RowTamper paths; honest fused plans leave ``verified=None``)."""
        if channel is None:
            return self._fused_ideal_round(P, A, seeds)
        if hasattr(channel, "plan_transform"):
            return self._fused_channel_round(P, A, channel, seeds,
                                             verify)
        return self._stagewise_channel_round(P, A, channel, verify)

    # -- a round over several generations ---------------------------------

    def _generations_solver(self, Ks: tuple, ns: tuple):
        """The jitted coding draw, selection and inversion of a round's
        generations, built once per (Ks, ns).

        ``solve(key, idx, valid)``: generation g draws its (n_g, K_g)
        coding matrix (or n_g seeds) from ``fold_in(key, g)``; its
        delivered rows are ``idx[g][valid[g]]``.  Every generation is
        padded to (max n, max K) and selected and inverted in one vmapped
        pass (:func:`select.select_invert_padded`).  Returns (ok: every
        generation reached rank, encode rows per generation — (K_g, K_g)
        matrices or (K_g,) seeds —, (K_g, K_g) inverses)."""
        key_ = ("generations", Ks, ns)
        if key_ in self._dispatch:
            return self._dispatch[key_]
        s, seeded = self.config.s, self.seeded
        k_max, n_max = max(Ks), max(ns)

        def solve(key, idx, valid):
            rows, padded = [], []
            for g, (K, n) in enumerate(zip(Ks, ns, strict=True)):
                kg = jax.random.fold_in(key, g)
                if seeded:
                    r = self.coding_seeds(kg, n)
                    A = seedlib.expand_rows(r, K, s)
                else:
                    r = A = self.coding_matrix(kg, n, K)
                rows.append(r)
                padded.append(jnp.pad(A, ((0, n_max - n), (0, k_max - K))))
            A_rx = jnp.take_along_axis(jnp.stack(padded), idx[..., None],
                                       axis=1)
            A_rx = jnp.where(valid[..., None], A_rx, jnp.uint8(0))
            ok, sel, A_inv = jax.vmap(
                lambda A, k: select_invert_padded(A, k, s))(
                    A_rx, jnp.asarray(Ks, jnp.int32))
            enc = tuple(rows[g][idx[g, sel[g, :K]]]
                        for g, K in enumerate(Ks))
            post = tuple(A_inv[g, :K, :K] for g, K in enumerate(Ks))
            return jnp.all(ok), enc, post

        self._dispatch[key_] = jax.jit(solve)
        return self._dispatch[key_]

    def _generations_round(self, Ps: Sequence[jnp.ndarray], key,
                           channel=None) -> EngineRound:
        """One round over several generations of unequal K (see
        :meth:`round`).  Consumes `Ps`: each is decoded in place."""
        if self.mesh is not None:
            raise NotImplementedError("generations run on one device")
        s = self.config.s
        Ks = tuple(int(P.shape[0]) for P in Ps)
        ns = tuple(K + self.config.extra_tuples for K in Ks)
        obs.count("engine.generations", len(Ps))
        obs.count("engine.coded_symbols",
                  sum(K * int(P.shape[1]) for K, P in zip(Ks, Ps)))
        with obs.span("engine.transform", cat="engine", n=sum(ns)):
            if channel is None:
                got = [np.arange(n) for n in ns]
            else:
                plans = [channel.plan_transform(n, s) for n in ns]
                if not all(isinstance(p, RowGather) for p in plans):
                    raise TypeError("generations take erasure channels "
                                    "(RowGather plans) only")
                got = [np.asarray(p.idx, np.int64) for p in plans]
        delivered = sum(len(g) for g in got)
        if any(len(g) < K for g, K in zip(got, Ks, strict=True)):
            return EngineRound(False, None,
                               ChannelReport(sum(ns), delivered, False))
        n_max = max(ns)
        idx = np.zeros((len(Ks), n_max), np.int32)
        valid = np.zeros((len(Ks), n_max), bool)
        for g, rows in enumerate(got):
            idx[g, :len(rows)] = rows
            valid[g, :len(rows)] = True
        with obs.span("engine.solve", cat="engine", generations=len(Ks)):
            ok, enc, post = self._generations_solver(Ks, ns)(
                key, jnp.asarray(idx), jnp.asarray(valid))
        ok = _on_host(ok)
        report = ChannelReport(sum(ns), delivered, ok)
        if not ok:
            return EngineRound(False, None, report)
        out = [self._stream(e, P, A_post=a, enc_seeded=self.seeded,
                            stage="decode", in_place=True)
               for e, P, a in zip(enc, Ps, post, strict=True)]
        return EngineRound(True, out, report)

    # -- the full round ---------------------------------------------------

    def round(self, P, key, channel=None, *,
              verify: bool = False) -> EngineRound:
        """encode -> (channel) -> select -> decode for one packet matrix,
        or for a list of them: one generation each.

        Ideal channel (None): the coding matrix is drawn, selected, and
        inverted *before* any L-sized work, then encode and decode of
        each chunk are interleaved in one stream — decode of chunk i
        overlaps encode of chunk i+1, and a singular draw costs O(K^3),
        not O(K·L).  Channels exposing `plan_transform` (erasure,
        multi-hop recode) are fused the same way; others fall back to
        the stage-wise path.  Bit-exact vs. the jnp-oracle reference.

        >>> import jax, jax.numpy as jnp
        >>> eng = CodingEngine(EngineConfig(s=8, kernel="jnp"))
        >>> P = jnp.arange(12, dtype=jnp.uint8).reshape(3, 4)
        >>> out = eng.round(P, jax.random.PRNGKey(0))
        >>> out.ok and (out.packets == P).all().item()
        True

        A list of (K_g, L_g) packet matrices is a round over several
        generations of unequal K (clients that upload only some parameter
        blocks): generation g sends K_g + ``extra_tuples`` tuples, coded
        from ``fold_in(key, g)``, with its own erasure pattern drawn in
        order from the same channel (erasure channels only).  Every
        generation is selected and inverted in one pass (span
        ``engine.solve``); the round decodes when every generation reaches
        rank, and then streams each generation's encode and decode,
        decoding in place (the matrices are consumed).  Counts
        ``engine.generations`` and ``engine.coded_symbols`` (Σ K_g·L_g).

        >>> out = eng.round([P[:1], P], jax.random.PRNGKey(1))
        >>> out.ok, [q.shape for q in out.packets]
        (True, [(1, 4), (3, 4)])
        """
        if isinstance(P, (list, tuple)):
            if verify:
                raise NotImplementedError("verify takes one generation")
            with obs.span("engine.round", cat="engine", generations=len(P)):
                return self._generations_round(P, key, channel)
        K, L = P.shape
        n = K + self.config.extra_tuples
        with obs.span("engine.round", cat="engine", K=K, L=L, n=n):
            if self.seeded:
                # seeded engine: draw 4-byte row seeds; the tiny
                # expansion drives row-space planning while the L-sized
                # encode stays seed-addressed inside the kernel.
                seeds = self.coding_seeds(key, n)
                out = self._run_round(P, self.expand_seeds(seeds, K),
                                      channel, seeds=seeds,
                                      verify=verify)
            else:
                A = self.coding_matrix(key, n, K)
                out = self._run_round(P, A, channel, verify=verify)
        return out

    # -- the fused hierarchical round (paper §III) ------------------------

    def multi_edge_coding_matrix(self, key, edges: Sequence[Sequence[int]],
                                 K: int, n_out: Sequence[int]
                                 ) -> jnp.ndarray:
        """Stacked global-space coding matrix of a whole edge tier.

        Edge e (serving clients `edges[e]`, a subset of range(K)) draws
        its (n_out[e], K_e) local mixing matrix with
        ``jax.random.fold_in(key, e)`` — the same stream the per-edge
        reference consumes — and its rows are embedded at that edge's
        client columns of the global K-wide coding-vector space.  Rows
        of different edges never overlap in support, so the stack is
        the block-structured matrix of paper §III's hierarchy.
        """
        blocks = []
        for e, ids in enumerate(edges):
            cols = jnp.asarray(tuple(int(i) for i in ids), jnp.int32)
            A_local = self.field.random_elements(
                jax.random.fold_in(key, e), (int(n_out[e]), len(ids)))
            A_g = jnp.zeros((int(n_out[e]), K), jnp.uint8)
            blocks.append(A_g.at[:, cols].set(A_local))
        return jnp.concatenate(blocks, axis=0)

    def multi_edge_round(self, P: jnp.ndarray, key,
                         edges: Sequence[Sequence[int]], *,
                         spare_per_edge: int = 0,
                         wan_channel=None,
                         verify: bool = False) -> EngineRound:
        """One fused hierarchical round: E edge encodes + WAN + decode.

        Instead of E separate `encode` re-entries (one per edge server)
        followed by a stage-wise channel and decode, the whole topology
        becomes one dispatch: every edge's local encode is a row block
        of :meth:`multi_edge_coding_matrix` in the global coding-vector
        space, the WAN channel (erasures / multi-hop recoding) is
        planned on the row space, and the single chunk-streamed
        `_stream` call runs encode, channel, and decode per chunk —
        decode of chunk i overlaps encode of chunk i+1.  Bit-exact vs.
        the per-edge reference (`core.hierarchy`, fused=False).

        `edges` lists each edge server's client indices (a partition of
        range(K)); each edge emits K_e + `spare_per_edge` combinations,
        so WAN erasures are repaired without re-contacting clients.

        >>> import jax, jax.numpy as jnp
        >>> eng = CodingEngine(EngineConfig(s=8, kernel="jnp"))
        >>> P = jnp.arange(12, dtype=jnp.uint8).reshape(3, 4)
        >>> out = eng.multi_edge_round(P, jax.random.PRNGKey(0),
        ...                            edges=[(0, 1), (2,)],
        ...                            spare_per_edge=1)
        >>> out.ok and (out.packets == P).all().item()
        True
        """
        K, L = P.shape
        n_out = [len(ids) + spare_per_edge for ids in edges]
        with obs.span("engine.multi_edge_round", cat="engine", K=K, L=L,
                      edges=len(edges)):
            A = self.multi_edge_coding_matrix(key, edges, K, n_out)
            return self._run_round(P, A, wan_channel, verify=verify)


@functools.lru_cache(maxsize=None)
def get_engine(config: EngineConfig = _DEFAULT_CONFIG) -> CodingEngine:
    """Process-wide engine cache keyed by (hashable) EngineConfig.

    Meshed engines are not cached (Mesh is unhashable); construct
    CodingEngine(config, mesh=...) directly for multi-device runs.
    """
    return CodingEngine(config)
