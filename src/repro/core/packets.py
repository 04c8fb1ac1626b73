"""Packetization: model-parameter pytrees <-> GF(2^s) symbol packets.

The paper treats "the local parameters uploaded by each client as a
packet" (§III).  It leaves the real-number -> finite-field mapping out
of scope; we implement it two ways:

* **bit-exact** (default): float32 (or any dtype) leaves are cut into
  their raw bytes, laid out as byte planes (byte 0 of every element,
  then byte 1, ...); bytes are split into s-bit symbols.  RLNC over
  GF(2^s) is then *lossless* — decode returns the packet bit-for-bit.
  Planes keep the split to shifts and contiguous slices: bitcasting to
  (n, itemsize) bytes has a minor dimension of 2 or 4, which the TPU
  pads to 128 lanes, so a large leaf would need many times its own
  size in scratch memory.
* **quantized** (the paper's cited alternative [22]): per-tensor affine
  int8 quantization before byte-packing (lossy, 4x smaller packets).

A packet is a 1-D uint8 array of symbols (each in [0, 2^s)) plus a
`PacketSpec` describing how to reassemble the pytree.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class PacketSpec:
    treedef: Any
    shapes: tuple[tuple[int, ...], ...]
    dtypes: tuple[Any, ...]
    s: int
    n_bytes: int          # total byte length before symbol split
    quantized: bool = False

    @property
    def symbols_per_byte(self) -> int:
        return 8 // self.s if self.s < 8 else 1

    @property
    def n_symbols(self) -> int:
        return self.n_bytes * self.symbols_per_byte


# ---------------------------------------------------------------------------
# bytes <-> symbols
# ---------------------------------------------------------------------------

def bytes_to_symbols(b: jnp.ndarray, s: int) -> jnp.ndarray:
    """Split a uint8 byte stream into s-bit symbols (s in {1,2,4,8}).

    Little-endian within the byte: symbol j of byte holds bits
    [j*s, (j+1)*s).  Output dtype uint8, each value < 2^s.
    """
    b = jnp.asarray(b, jnp.uint8)
    if s == 8:
        return b
    if s not in (1, 2, 4):
        raise ValueError("byte-aligned symbol sizes are 1, 2, 4, 8")
    per = 8 // s
    shifts = jnp.arange(per, dtype=jnp.uint8) * s          # (per,)
    mask = jnp.uint8((1 << s) - 1)
    sym = (b[:, None] >> shifts[None, :]) & mask           # (n, per)
    return sym.reshape(-1)


def symbols_to_bytes(sym: jnp.ndarray, s: int) -> jnp.ndarray:
    """Inverse of :func:`bytes_to_symbols`."""
    sym = jnp.asarray(sym, jnp.uint8)
    if s == 8:
        return sym
    # strided slices, not a reshape to (n, 8 // s): the TPU would pad
    # that minor dimension to 128 lanes
    per = 8 // s
    b = sym[0::per]
    for j in range(1, per):
        b = b | (sym[j::per] << np.uint8(j * s))
    return b


# ---------------------------------------------------------------------------
# pytree <-> packet
# ---------------------------------------------------------------------------

def _word_dtype(itemsize: int):
    """The unsigned integer dtype as wide as one element."""
    return jnp.dtype(f"uint{8 * itemsize}")


def _leaf_to_bytes(x: jnp.ndarray) -> jnp.ndarray:
    """A leaf's raw bytes as byte planes: (n·itemsize,) uint8."""
    flat = jnp.asarray(x).reshape(-1)
    if flat.dtype == jnp.uint8:
        return flat
    size = flat.dtype.itemsize
    word = jax.lax.bitcast_convert_type(flat, _word_dtype(size))
    if size == 1:
        return word
    return jnp.concatenate([((word >> (8 * q)) & 0xFF).astype(jnp.uint8)
                            for q in range(size)])


def _bytes_to_leaf(b: jnp.ndarray, shape, dtype) -> jnp.ndarray:
    """Inverse of :func:`_leaf_to_bytes`."""
    dtype = jnp.dtype(dtype)
    if dtype == jnp.uint8:
        return b.reshape(shape)
    size = dtype.itemsize
    n = int(np.prod(shape, dtype=np.int64)) if shape else 1
    wdt = _word_dtype(size)
    word = b[:n].astype(wdt)
    for q in range(1, size):
        word = word | (b[q * n:(q + 1) * n].astype(wdt) << (8 * q))
    return jax.lax.bitcast_convert_type(word, dtype).reshape(shape)


def pytree_to_packet(tree, s: int = 8) -> tuple[jnp.ndarray, PacketSpec]:
    """Flatten a pytree into one GF(2^s) symbol packet (bit-exact)."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    byte_chunks = [_leaf_to_bytes(l) for l in leaves]
    b = (jnp.concatenate(byte_chunks) if byte_chunks
         else jnp.zeros((0,), jnp.uint8))
    spec = PacketSpec(
        treedef=treedef,
        shapes=tuple(tuple(l.shape) for l in leaves),
        dtypes=tuple(jnp.asarray(l).dtype for l in leaves),
        s=s,
        n_bytes=int(b.shape[0]),
    )
    return bytes_to_symbols(b, s), spec


def packet_to_pytree(packet: jnp.ndarray, spec: PacketSpec):
    """Reassemble the pytree from a symbol packet (bit-exact inverse)."""
    b = symbols_to_bytes(packet, spec.s)[: spec.n_bytes]
    leaves = []
    off = 0
    for shape, dtype in zip(spec.shapes, spec.dtypes, strict=True):
        n = int(np.prod(shape, dtype=np.int64)) if shape else 1
        nbytes = n * jnp.dtype(dtype).itemsize
        leaves.append(_bytes_to_leaf(b[off: off + nbytes], shape, dtype))
        off += nbytes
    return jax.tree_util.tree_unflatten(spec.treedef, leaves)


def leaf_symbols(spec: PacketSpec, i: int) -> tuple[int, int]:
    """Symbols [lo, hi) of a packet that hold leaf `i`."""
    nbytes = [int(np.prod(sh, dtype=np.int64)) * jnp.dtype(dt).itemsize
              for sh, dt in zip(spec.shapes[:i + 1], spec.dtypes[:i + 1])]
    lo = sum(nbytes[:-1]) * spec.symbols_per_byte
    return lo, lo + nbytes[-1] * spec.symbols_per_byte


def symbols_to_leaf(sym: jnp.ndarray, dtype, s: int) -> jnp.ndarray:
    """A leaf's symbols (its :func:`leaf_symbols` span) -> the leaf, flat.

    Slices, shifts and a bitcast only, so traced code that consumes the
    leaf fuses the whole inverse into its own pass."""
    b = symbols_to_bytes(sym, s)
    return _bytes_to_leaf(b, (b.shape[0] // jnp.dtype(dtype).itemsize,),
                          dtype)


# ---------------------------------------------------------------------------
# stacking clients
# ---------------------------------------------------------------------------

def stack_packets(packets: list[jnp.ndarray]) -> jnp.ndarray:
    """K same-length packets -> P matrix (K, L) for RLNC (paper eq. P)."""
    L = packets[0].shape[0]
    for p in packets:
        if p.shape != (L,):
            raise ValueError("all client packets must have equal length")
    return jnp.stack(packets, axis=0)


# ---------------------------------------------------------------------------
# batched packetization (the engine hot path)
# ---------------------------------------------------------------------------

def pytrees_to_packets(trees: list, s: int = 8
                       ) -> tuple[jnp.ndarray, PacketSpec]:
    """K same-structure pytrees -> (K, L) symbol matrix.

    Equal to ``stack_packets([pytree_to_packet(t, s)[0] ...])``, but
    written one leaf at a time into one (K, L) matrix that each leaf's
    write donates: the device holds the matrix and one leaf's K copies,
    not every leaf's bytes and their join (for 3.3 GB of update on a TPU
    v5e that join took 6.5 GB more).
    """
    if not trees:
        raise ValueError("need at least one client pytree")
    leaves0, treedef = jax.tree_util.tree_flatten(trees[0])
    per_client = [jax.tree_util.tree_flatten(t)[0] for t in trees]
    spec = PacketSpec(
        treedef=treedef,
        shapes=tuple(tuple(l.shape) for l in leaves0),
        dtypes=tuple(jnp.asarray(l).dtype for l in leaves0),
        s=s,
        n_bytes=sum(int(np.prod(l.shape, dtype=np.int64))
                    * jnp.dtype(l.dtype).itemsize for l in leaves0),
    )
    P = jnp.zeros((len(trees), spec.n_symbols), jnp.uint8)
    for i, xs in enumerate(zip(*per_client, strict=True)):
        # stack and flatten eagerly: fused with the byte split, the
        # flatten's relayout compiles for tens of seconds on a TPU
        flat = jnp.stack(xs).reshape(len(xs), -1)
        P = _put_leaf(s)(P, flat, leaf_symbols(spec, i)[0])
    return P, spec


@functools.lru_cache(maxsize=None)
def _put_leaf(s: int):
    """``put(P, flat, lo)``: K flat leaves' symbols written into P's
    lanes from `lo`, in place (P is donated)."""
    split = jax.vmap(functools.partial(bytes_to_symbols, s=s))

    def put(P, flat, lo):
        return jax.lax.dynamic_update_slice_in_dim(
            P, split(jax.vmap(_leaf_to_bytes)(flat)), lo, axis=1)
    return jax.jit(put, donate_argnums=0)


def packets_to_pytrees(P_hat: jnp.ndarray, spec: PacketSpec):
    """(K, L) decoded symbols -> ONE stacked pytree (leading K axis).

    Batched inverse of :func:`pytrees_to_packets`; index the leading
    axis (or tree_map over it) to recover per-client trees.
    """
    b = jax.vmap(lambda row: symbols_to_bytes(row, spec.s))(P_hat)
    if b.shape[1] != spec.n_bytes:
        b = b[:, : spec.n_bytes]
    leaves = []
    off = 0
    for shape, dtype in zip(spec.shapes, spec.dtypes, strict=True):
        n = int(np.prod(shape, dtype=np.int64)) if shape else 1
        nbytes = n * jnp.dtype(dtype).itemsize
        leaves.append(jax.vmap(
            lambda bb, sh=shape, dt=dtype: _bytes_to_leaf(bb, sh, dt)
        )(b[:, off: off + nbytes]))
        off += nbytes
    return jax.tree_util.tree_unflatten(spec.treedef, leaves)


# ---------------------------------------------------------------------------
# partial updates: clients that upload only some blocks of a leaf
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
class Blocks:
    """The blocks of one leaf that a client holds.

    The leaf's leading axes, `grid`, index blocks: a routed-expert leaf
    (layers, experts, d, ff) has grid (layers, experts) and blocks of
    shape (d, ff).  A client that trained only some of them uploads
    their `rows`, (len(ids), *block shape), with `ids` the blocks' flat
    indices into `grid`, ascending.  In one update, the leaves of a grid
    hold the same ids, and block i of each of them is one block of the
    update: an expert is its w_gate, w_up and w_down slices.
    """

    def __init__(self, rows, ids, grid):
        self.rows = rows
        self.ids = tuple(int(i) for i in ids)
        self.grid = tuple(int(g) for g in grid)

    def tree_flatten(self):
        return (self.rows,), (self.ids, self.grid)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], *aux)


def is_blocks(x) -> bool:
    return isinstance(x, Blocks)


@dataclass(frozen=True)
class BlockGroup:
    """The leaves of one grid: a generation per block somebody holds."""

    grid: tuple[int, ...]
    leaves: tuple[int, ...]              # positions in the update's leaves
    shapes: tuple[tuple[int, ...], ...]  # each leaf's block shape
    dtypes: tuple[Any, ...]
    holders: tuple[tuple[int, ...], ...]  # per block: its clients, ascending

    def block_symbols(self, s: int) -> list[tuple[int, int]]:
        """Symbols [lo, hi) of each leaf's slice in a block's packet."""
        per = 8 // s if s < 8 else 1
        out, lo = [], 0
        for shape, dtype in zip(self.shapes, self.dtypes, strict=True):
            n = int(np.prod(shape, dtype=np.int64)) * jnp.dtype(dtype).itemsize
            out.append((lo, lo + n * per))
            lo += n * per
        return out


@dataclass(frozen=True)
class BlockLayout:
    """Where K partial updates put their bytes: the leaves every client
    holds whole (`dense`, one generation among all K) and the blocked
    `groups` (a generation per block, among the clients holding it)."""

    treedef: Any                          # the update, Blocks as leaves
    dense: tuple[int, ...]                # positions of the whole leaves
    groups: tuple[BlockGroup, ...]
    held: tuple[tuple[tuple[int, ...], ...], ...]  # [client][group] ids

    def generations(self) -> list[tuple[int, int]]:
        """(group, block) of each block generation, in round order."""
        return [(gi, b) for gi, g in enumerate(self.groups)
                for b, h in enumerate(g.holders) if h]


def split_blocks(trees: list) -> tuple[BlockLayout, list, list]:
    """K updates, some leaves given as :class:`Blocks` ->
    ``(layout, dense, rows)``: ``dense[k]`` client k's whole leaves (in
    ``layout.dense`` order), ``rows[k][g]`` its rows of group g's leaves.

    A grid whose every block every client holds is whole: its leaves
    join the dense ones (reshaped, not copied)."""
    flat = [jax.tree_util.tree_flatten(t, is_leaf=is_blocks) for t in trees]
    treedef = flat[0][1]
    if any(td != treedef for _, td in flat):
        raise ValueError("the clients' updates differ in structure")
    items = [f[0] for f in flat]
    blocked = [i for i, x in enumerate(items[0]) if is_blocks(x)]
    if any(is_blocks(x) != (i in blocked) for its in items
           for i, x in enumerate(its)):
        raise ValueError("clients block different leaves")
    grids: dict = {}
    for i in blocked:
        grids.setdefault(items[0][i].grid, []).append(i)
    dense = [i for i in range(len(items[0])) if i not in blocked]
    groups, held = [], [[] for _ in trees]
    for grid, leaves in grids.items():
        ids = []
        for its in items:
            mine = {its[i].ids for i in leaves}
            if len(mine) != 1 or any(its[i].grid != grid for i in leaves):
                raise ValueError(f"a client holds different blocks of "
                                 f"the leaves of grid {grid}")
            ids.append(mine.pop())
        n = int(np.prod(grid))
        if any(list(i) != sorted(set(i)) or (i and not 0 <= i[0] <= i[-1] < n)
               for i in ids):
            raise ValueError("block ids must be ascending and in the grid")
        if all(len(i) == n for i in ids):
            dense += leaves
            continue
        shapes = tuple(tuple(_block_shape(its[i]) for its in items)
                       for i in leaves)
        if any(len(set(sh)) != 1 for sh in shapes):
            raise ValueError("clients' blocks of a leaf differ in shape")
        dtypes = tuple({jnp.dtype(its[i].rows.dtype) for its in items}
                       for i in leaves)
        if any(len(d) != 1 for d in dtypes):
            raise ValueError("clients' blocks of a leaf differ in dtype")
        groups.append(BlockGroup(
            grid, tuple(leaves), tuple(sh[0] for sh in shapes),
            tuple(d.pop() for d in dtypes),
            tuple(tuple(k for k, i in enumerate(ids) if b in i)
                  for b in range(n))))
        for k in range(len(trees)):
            held[k].append(ids[k])
    dense = sorted(dense)
    whole = [[_whole(its[i]) for i in dense] for its in items]
    rows = [[[its[i].rows for i in g.leaves] for g in groups]
            for its in items]
    layout = BlockLayout(treedef, tuple(dense), tuple(groups),
                         tuple(tuple(h) for h in held))
    return layout, whole, rows


def _block_shape(b: Blocks) -> tuple[int, ...]:
    if b.rows.shape[0] != len(b.ids):
        raise ValueError("a Blocks leaf needs one row per id")
    return tuple(b.rows.shape[1:])


def _whole(x):
    """A leaf every client holds whole, as one array."""
    return x.rows.reshape(x.grid + x.rows.shape[1:]) if is_blocks(x) else x


def block_packets(rows: list, layout: BlockLayout, s: int = 8
                  ) -> list[jnp.ndarray]:
    """The block generations' packet matrices, in
    ``layout.generations()`` order: block b's (K_b, L_b) symbols hold
    each holder's slices of the group's leaves (byte planes, leaf after
    leaf), holders in client order, split into s-bit symbols.  One
    compiled program per (layout, s); `rows` as :func:`split_blocks`
    returns them."""
    return _block_packets_fn(layout, s)(rows)


@functools.lru_cache(maxsize=None)
def _block_packets_fn(layout: BlockLayout, s: int):
    split = jax.vmap(functools.partial(bytes_to_symbols, s=s))

    def cut(rows):
        out = []
        for gi, b in layout.generations():
            # stack the holders' slices of one leaf, then split the bytes
            # of all of them at once (as pytrees_to_packets does)
            hs = layout.groups[gi].holders[b]
            at = [layout.held[k][gi].index(b) for k in hs]
            out.append(split(jnp.concatenate(
                [jax.vmap(_leaf_to_bytes)(jnp.stack(
                    [rows[k][gi][j][p] for k, p in zip(hs, at)]))
                 for j in range(len(layout.groups[gi].leaves))], axis=1)))
        return out
    return jax.jit(cut)


# ---------------------------------------------------------------------------
# wire formats: materialized rows vs seed-addressed packets
# ---------------------------------------------------------------------------
#
# An encoded tuple on the wire is header + payload.  The materialized
# format ships the K-symbol coding row (K·s/8 bytes); the seeded
# format (repro.core.seeds) ships a 4-byte uint32 seed from which the
# receiver regenerates the row — the paper's overhead objection at
# large K drops from K+L to 4+L bytes per packet.

SEED_WIRE_BYTES = 4


def coding_row_wire_bytes(K: int, s: int) -> int:
    """Bytes a materialized K-symbol GF(2^s) coding row occupies."""
    return -(-K * s // 8)


def packet_wire_bytes(K: int, payload_symbols: int, s: int,
                      *, seeded: bool) -> int:
    """Total wire bytes of one encoded tuple (header + payload).

    >>> packet_wire_bytes(128, 4096, 8, seeded=False)   # K + L
    4224
    >>> packet_wire_bytes(128, 4096, 8, seeded=True)    # 4 + L
    4100
    """
    header = SEED_WIRE_BYTES if seeded else coding_row_wire_bytes(K, s)
    return header + -(-payload_symbols * s // 8)


def pack_seed_packet(seed, payload: jnp.ndarray, s: int) -> jnp.ndarray:
    """Serialize one seeded tuple: 4 seed bytes (LE) + payload bytes."""
    seed_bytes = jax.lax.bitcast_convert_type(
        jnp.asarray(seed, jnp.uint32).reshape(1), jnp.uint8).reshape(-1)
    return jnp.concatenate(
        [seed_bytes, symbols_to_bytes(payload, s)])


def unpack_seed_packet(buf: jnp.ndarray, s: int
                       ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Inverse of :func:`pack_seed_packet`: (seed uint32, payload)."""
    buf = jnp.asarray(buf, jnp.uint8)
    seed = jax.lax.bitcast_convert_type(
        buf[:SEED_WIRE_BYTES].reshape(1, SEED_WIRE_BYTES),
        jnp.uint32).reshape(())
    return seed, bytes_to_symbols(buf[SEED_WIRE_BYTES:], s)


# ---------------------------------------------------------------------------
# quantized variant (paper ref [22]: pruning-quantization coding design)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class QuantSpec:
    scales: tuple[float, ...]
    zeros: tuple[float, ...]


def quantize_pytree(tree, bits: int = 8):
    """Per-tensor affine quantization to uint8 in [0, 2^bits)."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    qleaves, scales, zeros = [], [], []
    qmax = float(2**bits - 1)
    for l in leaves:
        l = jnp.asarray(l, jnp.float32)
        lo = jnp.min(l)
        hi = jnp.max(l)
        scale = jnp.maximum((hi - lo) / qmax, 1e-12)
        q = jnp.clip(jnp.round((l - lo) / scale), 0, qmax).astype(jnp.uint8)
        qleaves.append(q)
        scales.append(float(scale))
        zeros.append(float(lo))
    qtree = jax.tree_util.tree_unflatten(treedef, qleaves)
    return qtree, QuantSpec(tuple(scales), tuple(zeros))


def dequantize_pytree(qtree, qspec: QuantSpec):
    leaves, treedef = jax.tree_util.tree_flatten(qtree)
    out = [
        jnp.asarray(q, jnp.float32) * s + z
        for q, s, z in zip(leaves, qspec.scales, qspec.zeros,
                           strict=True)
    ]
    return jax.tree_util.tree_unflatten(treedef, out)
