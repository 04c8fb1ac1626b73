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
import math
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
    K = P_hat.shape[0]
    zero_bits = jax.lax.bitcast_convert_type(zero, jnp.uint32)
    out = []
    for i, (shape, dtype) in enumerate(zip(spec.shapes, spec.dtypes,
                                           strict=True)):
        lo, hi = pkt.leaf_symbols(spec, i)

        def add(k, acc, lo=lo, hi=hi, dtype=dtype):
            sym = jax.lax.dynamic_slice(P_hat, (k, lo), (1, hi - lo))[0]
            x = pkt.symbols_to_leaf(sym, dtype, spec.s).astype(jnp.float32)
            wx = jax.lax.bitcast_convert_type(w[k] * x, jnp.uint32)
            return acc + jax.lax.bitcast_convert_type(wx ^ zero_bits,
                                                      jnp.float32)

        acc = jnp.broadcast_to(zero, (math.prod(shape),))
        acc = jax.lax.fori_loop(0, K, add, acc)
        out.append(acc.astype(dtype).reshape(shape))
    return jax.tree_util.tree_unflatten(spec.treedef, out)


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


def fednc_round(client_params: Sequence[Any], weights: Sequence[float],
                prev_global: Any, cfg: FedNCConfig, key,
                channel=None) -> RoundResult:
    """Full Alg.-1 round: a thin adapter over CodingEngine.round().

    Each call leaves one ``fednc.round`` record in ``repro.obs``: the
    host time of its phases (``fednc.packetize``, ``engine.*``,
    ``fednc.sync``, ``fednc.aggregate``) and its counts."""
    with obs.record("fednc.round"):
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
