"""FedNC round logic — Algorithm 1 of the paper, as a composable module.

One communication round:

    P   <- stack(packetize(w_k) for k in participants)     (paper: P)
    A   <- random coding matrix over GF(2^s)               (paper: a_i)
    C   <- A · P                                           (eq. 4)
    ... tuples (a_i, C_i) traverse the channel ...
    if A' (received) invertible:
        P_hat <- GE(A', C');  w <- Σ p_k · unpacketize(P_hat_k)
    else:
        w <- w_prev                                        (skip round)

The coded math — batched packetization, chunk-streamed kernel
execution, jit-safe row selection, decode — lives in
repro.engine.CodingEngine; this module is the thin Alg.-1 adapter that
maps FedNCConfig onto an engine and turns decoded packets back into a
weighted FedAvg aggregate.

The encode/decode field path is bit-exact (see core.packets), so when
decoding succeeds the aggregated model equals plain FedAvg on the same
client set — coding costs zero accuracy, exactly the paper's claim for
the iid/no-loss setting.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.engine.defaults import DEFAULT_CHUNK_L

from . import packets as pkt
from .channel import ChannelReport
from .rlnc import EncodedBatch


@dataclass(frozen=True)
class FedNCConfig:
    s: int = 8                 # field size (symbol bits), paper Table I
    kernel_impl: str = "auto"  # engine-registry kernel name
    extra_tuples: int = 0      # send K + extra coded tuples (erasure headroom)
    systematic: bool = False   # identity-prefixed coding matrix
    quantize_bits: int = 0     # 0 = bit-exact float bytes (default);
    #                            8 = paper-[22] affine int8 packets (4x
    #                            smaller uploads, lossy)
    coding_density: float = 1.0  # <1.0 = sparse RLNC coefficients
    chunk_l: int = DEFAULT_CHUNK_L  # streamed-chunk symbols (0 = one shot)


def engine_for(cfg: FedNCConfig) -> "repro.engine.CodingEngine":
    """The (cached) CodingEngine realizing this round configuration."""
    # call-time import: repro.engine eagerly imports repro.core, so this
    # adapter direction must stay lazy to keep both import orders legal
    from repro.engine import EngineConfig, get_engine
    return get_engine(EngineConfig(
        s=cfg.s,
        kernel=cfg.kernel_impl,
        chunk_l=cfg.chunk_l,
        extra_tuples=cfg.extra_tuples,
        systematic=cfg.systematic,
        coding_density=cfg.coding_density,
    ))


@dataclass
class RoundResult:
    global_params: Any
    decoded: bool
    report: Optional[ChannelReport]
    n_aggregated: int


def _packetize(client_params: Sequence[Any], cfg: FedNCConfig
               ) -> tuple[jnp.ndarray, pkt.PacketSpec, Optional[list]]:
    """(P, spec, qspecs): vmap-batched packetization of K clients.

    Quantization (the lossy paper-[22] variant) stays per-client — it
    produces a few Python floats of metadata each — but the byte/symbol
    packetization itself is always the single batched pass."""
    engine = engine_for(cfg)
    with obs.span("fednc.packetize", cat="fednc"):
        if cfg.quantize_bits:
            qspecs, qtrees = [], []
            for p in client_params:
                q, qs = pkt.quantize_pytree(p, bits=cfg.quantize_bits)
                qtrees.append(q)
                qspecs.append(qs)
            P, spec = engine.packetize(qtrees)
            return P, spec, qspecs
        P, spec = engine.packetize(client_params)
        return P, spec, None


@functools.partial(jax.jit, static_argnums=3)
def _weighted_mean(P_hat: jnp.ndarray, w: jnp.ndarray, zero: jnp.ndarray,
                   spec: pkt.PacketSpec) -> Any:
    """Decoded (K, L) symbols -> the FedAvg aggregate, as one program.

    Compiled once per (spec, K): the weights are traced, so new weights
    reuse it.  Per leaf, a loop over the clients in order reads client
    k's leaf from its row (``packets.symbols_to_leaf``) and adds
    w[k]·x_k to a float32 accumulator that starts at 0; the sum is cast
    back to the leaf's dtype.  That is the arithmetic and order of
    ``fedavg_round``, so FedNC == FedAvg bit for bit.  The program holds
    one float32 leaf, never a stacked or per-client copy of the tree,
    and the loop (not an unrolled chain) keeps its size independent of K.

    `zero` is a float32 0 passed as data, where the compiler cannot see
    it: it would fold 0 + x to x, which keeps a -0.0 that the sum from 0
    makes +0.0.  Each product is xor-ed with its bits before the add,
    or XLA's CPU backend contracts acc + w·x into one fused
    multiply-add, which rounds once where FedAvg rounds twice."""
    obs.count("fednc.aggregate_traces")
    return jax.tree_util.tree_unflatten(spec.treedef,
                                        _leaf_means(P_hat, w, zero, spec))


def _leaf_means(P_hat, w, zero, spec: pkt.PacketSpec) -> list:
    """Each leaf of `spec` averaged over P_hat's rows (traced)."""
    return [_mean_rows(P_hat, w, zero, *pkt.leaf_symbols(spec, i), dtype,
                       spec.s).reshape(shape)
            for i, (shape, dtype) in enumerate(zip(spec.shapes, spec.dtypes,
                                                   strict=True))]


def _mean_rows(P_hat, w, zero, lo: int, hi: int, dtype, s: int):
    """Σ_k w[k]·x_k over P_hat's rows, x_k the flat leaf in symbols
    [lo, hi) of row k: float32 from `zero` in row order, cast to
    `dtype` (the arithmetic :func:`_weighted_mean` documents)."""
    zero_bits = jax.lax.bitcast_convert_type(zero, jnp.uint32)

    def add(k, acc):
        sym = jax.lax.dynamic_slice(P_hat, (k, lo), (1, hi - lo))[0]
        x = pkt.symbols_to_leaf(sym, dtype, s).astype(jnp.float32)
        wx = jax.lax.bitcast_convert_type(w[k] * x, jnp.uint32)
        return acc + jax.lax.bitcast_convert_type(wx ^ zero_bits,
                                                  jnp.float32)

    n = (hi - lo) // (8 // s if s < 8 else 1) // jnp.dtype(dtype).itemsize
    acc = jnp.broadcast_to(zero, (n,))
    return jax.lax.fori_loop(0, P_hat.shape[0], add, acc).astype(dtype)


@functools.partial(jax.jit, static_argnums=(4, 5))
def _block_mean(P_hats: tuple, w: jnp.ndarray, zero: jnp.ndarray,
                prev: tuple, spec: pkt.PacketSpec,
                layout: pkt.BlockLayout) -> Any:
    """Decoded generations -> the per-block FedAvg aggregate, as one
    program per (layout, contributor sets).

    Whole leaves are averaged over all K clients (``P_hats[0]``, weights
    ``w[:K]``); block b of a group over its holders (its generation's
    rows, weights the next K_b of `w`), each as :func:`_mean_rows`.  A
    blocked leaf starts from its `prev` value, so a block nobody holds
    keeps it."""
    obs.count("fednc.aggregate_traces")
    K = len(layout.held)
    out = [None] * layout.treedef.num_leaves
    for i, x in zip(layout.dense, _leaf_means(P_hats[0], w[:K], zero, spec),
                    strict=True):
        out[i] = x
    prev = iter(prev)
    for g in layout.groups:
        for i in g.leaves:
            out[i] = next(prev)
    off = K
    for P_hat, (gi, b) in zip(P_hats[1:], layout.generations(), strict=True):
        g = layout.groups[gi]
        w_b = w[off:off + len(g.holders[b])]
        off += len(g.holders[b])
        at = np.unravel_index(b, g.grid)
        for i, (lo, hi), shape, dtype in zip(g.leaves, g.block_symbols(spec.s),
                                             g.shapes, g.dtypes, strict=True):
            x = _mean_rows(P_hat, w_b, zero, lo, hi, dtype, spec.s)
            out[i] = out[i].at[at].set(x.reshape(shape))
    return jax.tree_util.tree_unflatten(layout.treedef, out)


def _aggregate(P_hat: jnp.ndarray, spec: pkt.PacketSpec,
               weights: Sequence[float], cfg: FedNCConfig,
               qspecs: Optional[list] = None) -> Any:
    """Decoded packets -> weighted FedAvg aggregate (paper §II-A)."""
    with obs.span("fednc.aggregate", cat="fednc"):
        w = np.asarray(weights, np.float32)
        w = w / w.sum()
        if qspecs is not None:
            stacked = pkt.packets_to_pytrees(P_hat, spec)
            trees = [jax.tree_util.tree_map(lambda x, k=k: x[k], stacked)
                     for k in range(P_hat.shape[0])]
            trees = [pkt.dequantize_pytree(t, qs)
                     for t, qs in zip(trees, qspecs, strict=True)]
            return jax.tree_util.tree_map(
                lambda *xs: sum(
                    wk * jnp.asarray(x, jnp.float32)
                    for wk, x in zip(w, xs, strict=True)
                ).astype(xs[0].dtype),
                *trees,
            )
        obs.count("fednc.aggregate_traces", 0)   # reads 0 when warm
        return _weighted_mean(P_hat, w, np.float32(0), spec)


def packetize_clients(client_params: Sequence[Any], cfg: FedNCConfig
                      ) -> tuple[jnp.ndarray, pkt.PacketSpec,
                                 Optional[list]]:
    """Public head of Alg. 1 for callers that run their own coded
    pipeline (e.g. the async strategy): honors `quantize_bits` and
    returns the qspecs the decode side needs."""
    return _packetize(client_params, cfg)


def aggregate_decoded(P_hat: jnp.ndarray, spec: pkt.PacketSpec,
                      weights: Sequence[float], cfg: FedNCConfig,
                      qspecs: Optional[list] = None) -> Any:
    """Public tail of Alg. 1 for callers that decode their own packets
    (e.g. the streaming rank-K decoder): decoded (K, L) symbols ->
    weighted FedAvg aggregate, identical math to `fednc_round`."""
    return _aggregate(P_hat, spec, weights, cfg, qspecs=qspecs)


def encode_clients(client_params: Sequence[Any], cfg: FedNCConfig, key
                   ) -> tuple[EncodedBatch, pkt.PacketSpec, Optional[list]]:
    """Packetize + RLNC-encode K client parameter pytrees.

    Returns (batch, spec, qspecs); qspecs is per-client quantization
    metadata when cfg.quantize_bits > 0 (it travels uncoded alongside
    the coding vectors — a few floats per tensor, like a_i itself)."""
    engine = engine_for(cfg)
    P, spec, qspecs = _packetize(client_params, cfg)
    K = P.shape[0]
    A = engine.coding_matrix(key, K + cfg.extra_tuples, K)
    return engine.encode(P, A), spec, qspecs


def decode_and_aggregate(batch: EncodedBatch, spec: pkt.PacketSpec,
                         weights: Sequence[float], prev_global: Any,
                         cfg: FedNCConfig,
                         qspecs: Optional[list] = None) -> RoundResult:
    """Server side of Alg. 1: decode (selecting K rows on-device when
    n > K), weighted FedAvg, or skip."""
    K = batch.K
    if batch.n < K:
        return RoundResult(prev_global, False, None, 0)
    ok, P_hat = engine_for(cfg).decode(batch)
    if not ok:
        return RoundResult(prev_global, False, None, 0)
    agg = _aggregate(P_hat, spec, weights, cfg, qspecs=qspecs)
    return RoundResult(agg, True, None, K)


def _block_round(layout: pkt.BlockLayout, dense: list, rows: list,
                 weights: Sequence[float], prev_global: Any,
                 cfg: FedNCConfig, key, channel) -> RoundResult:
    """The round of partial updates (:func:`fednc_round`)."""
    if cfg.quantize_bits:
        raise NotImplementedError("partial updates are coded bit-exact")
    if jax.tree_util.tree_structure(prev_global) != layout.treedef:
        raise ValueError("prev_global's structure differs from the updates'")
    engine = engine_for(cfg)
    K = len(dense)
    with obs.span("fednc.packetize", cat="fednc"):
        P, spec = engine.packetize(dense)
    with obs.span("fednc.blocks", cat="fednc"):
        P_blocks = pkt.block_packets(rows, layout, cfg.s)
    out = engine.round([P, *P_blocks], key, channel=channel)
    del P, P_blocks     # decoded in place
    if not out.ok:
        return RoundResult(prev_global, False, out.report, 0)
    with obs.span("fednc.aggregate", cat="fednc"):
        w = np.asarray(weights, np.float32)
        ws = [w / w.sum()]
        for gi, b in layout.generations():
            w_b = w[list(layout.groups[gi].holders[b])]
            ws.append(w_b / w_b.sum())
        prev = jax.tree_util.tree_leaves(prev_global)
        obs.count("fednc.aggregate_traces", 0)   # reads 0 when warm
        agg = _block_mean(tuple(out.packets), np.concatenate(ws),
                          np.float32(0),
                          tuple(prev[i] for g in layout.groups
                                for i in g.leaves), spec, layout)
    return RoundResult(agg, True, out.report, K)


def fednc_round(client_params: Sequence[Any], weights: Sequence[float],
                prev_global: Any, cfg: FedNCConfig, key,
                channel=None) -> RoundResult:
    """Full Alg.-1 round: a thin adapter over CodingEngine.round().

    A client that trained only some blocks of a leaf (routed experts)
    gives that leaf as :class:`packets.Blocks`, its rows of the blocks it
    holds.  The round then codes the leaves every client holds as one
    generation among all K, and each block somebody holds as its own
    generation among its holders (``CodingEngine.round`` over a list).
    The aggregate averages whole leaves over all K and each block over
    its holders, with weights ``w_k / Σ_{k holding it} w_k`` in float32;
    a block nobody holds takes `prev_global`'s value.  A grid whose every
    block every client holds is a whole leaf, so such a round is today's
    single-generation round.

    Each call leaves one ``fednc.round`` record in ``repro.obs``: the
    host time of its phases (``fednc.packetize``, ``fednc.blocks``,
    ``engine.*``, ``fednc.sync``, ``fednc.aggregate``) and its counts."""
    with obs.record("fednc.round"):
        if any(pkt.is_blocks(x) for t in client_params
               for x in jax.tree_util.tree_leaves(t, is_leaf=pkt.is_blocks)):
            layout, dense, rows = pkt.split_blocks(list(client_params))
            if layout.groups:
                return _block_round(layout, dense, rows, weights,
                                    prev_global, cfg, key, channel)
            client_params = [jax.tree_util.tree_unflatten(layout.treedef, d)
                             for d in dense]
        engine = engine_for(cfg)
        P, spec, qspecs = _packetize(client_params, cfg)
        K = P.shape[0]
        out = engine.round(P, key, channel=channel)
        del P   # free the payload before the decoded copy is unpacketized
        if not out.ok:
            return RoundResult(prev_global, False, out.report, 0)
        agg = _aggregate(out.packets, spec, weights, cfg, qspecs=qspecs)
        return RoundResult(agg, True, out.report, K)


def fedavg_round(client_params: Sequence[Any], weights: Sequence[float],
                 prev_global: Any, channel=None) -> RoundResult:
    """Classic FedAvg baseline (paper §II-A), same channel interface."""
    w = np.asarray(weights, np.float32)
    if channel is not None:
        stacked = pkt.pytrees_to_packets(client_params, s=8)[0]
        delivered, idx, report = channel.transmit_plain(stacked)
        if len(idx) == 0:
            return RoundResult(prev_global, False, report, 0)
        client_params = [client_params[i] for i in idx]
        w = w[list(idx)]
    else:
        report = None
    w = w / w.sum()
    agg = jax.tree_util.tree_map(
        lambda *xs: sum(
            wk * jnp.asarray(x, jnp.float32)
            for wk, x in zip(w, xs, strict=True)
        ).astype(xs[0].dtype),
        *client_params,
    )
    return RoundResult(agg, True, report, len(client_params))
