"""Attention: GQA (RoPE, qk-norm, bias, sliding window), MLA
(DeepSeek-V2 latent attention), and cross-attention — with prefill /
decode KV-cache paths.

Long sequences use a q-chunked formulation (lax.scan over query blocks)
so scores never materialize at (S, S): this is the flash-attention
memory pattern expressed in pure JAX (the Pallas kernel variant is an
optional perf path; XLA fuses this one well on TPU).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .config import MLAConfig, ModelConfig
from .layers import apply_rope, dense_apply, dense_init, norm_apply, norm_init

CHUNK_THRESHOLD = 8192   # direct attention below, q-chunked above
Q_CHUNK = 512

# §Perf knob: keep attention operands in bf16 (accumulate in f32 via
# preferred_element_type) instead of materializing f32 copies of Q/K/V
# and the probability matrix — halves attention HBM traffic.
ATTEND_BF16 = False


def set_attend_bf16(flag: bool) -> None:
    globals()["ATTEND_BF16"] = flag


# ---------------------------------------------------------------------------
# parameter init
# ---------------------------------------------------------------------------

def init_self_attention(key, cfg: ModelConfig) -> dict:
    if cfg.mla is not None:
        return _init_mla(key, cfg)
    d, H, KV, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, \
        cfg.resolved_head_dim
    ks = jax.random.split(key, 6)
    p = {
        "wq": dense_init(ks[0], d, H * hd, bias=cfg.qkv_bias,
                         dtype=cfg.dtype),
        "wk": dense_init(ks[1], d, KV * hd, bias=cfg.qkv_bias,
                         dtype=cfg.dtype),
        "wv": dense_init(ks[2], d, KV * hd, bias=cfg.qkv_bias,
                         dtype=cfg.dtype),
        "wo": dense_init(ks[3], H * hd, d, dtype=cfg.dtype),
    }
    if cfg.qk_norm:
        p["qnorm"] = norm_init(hd, "rmsnorm", cfg.dtype)
        p["knorm"] = norm_init(hd, "rmsnorm", cfg.dtype)
    return p


def _init_mla(key, cfg: ModelConfig) -> dict:
    m: MLAConfig = cfg.mla
    d, H = cfg.d_model, cfg.num_heads
    ks = jax.random.split(key, 8)
    qd = m.nope_head_dim + m.rope_head_dim
    if m.q_lora_rank is None:
        q = {"w_q": dense_init(ks[0], d, H * qd, dtype=cfg.dtype)}
    else:
        q = {"w_dq": dense_init(ks[0], d, m.q_lora_rank, dtype=cfg.dtype),
             "q_norm": norm_init(m.q_lora_rank, "rmsnorm", cfg.dtype),
             "w_uq": dense_init(ks[1], m.q_lora_rank, H * qd,
                                dtype=cfg.dtype)}
    return {
        **q,
        "w_dkv": dense_init(ks[2], d, m.kv_lora_rank, dtype=cfg.dtype),
        "kv_norm": norm_init(m.kv_lora_rank, "rmsnorm", cfg.dtype),
        "w_uk": dense_init(ks[3], m.kv_lora_rank, H * m.nope_head_dim,
                           dtype=cfg.dtype),
        "w_uv": dense_init(ks[4], m.kv_lora_rank, H * m.v_head_dim,
                           dtype=cfg.dtype),
        "w_kr": dense_init(ks[5], d, m.rope_head_dim, dtype=cfg.dtype),
        "wo": dense_init(ks[6], H * m.v_head_dim, d, dtype=cfg.dtype),
    }


def init_cross_attention(key, cfg: ModelConfig) -> dict:
    """KV from frontend/encoder memory; same head layout as self-attn."""
    d, H, KV, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, \
        cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], d, H * hd, dtype=cfg.dtype),
        "wk": dense_init(ks[1], d, KV * hd, dtype=cfg.dtype),
        "wv": dense_init(ks[2], d, KV * hd, dtype=cfg.dtype),
        "wo": dense_init(ks[3], H * hd, d, dtype=cfg.dtype),
    }


# ---------------------------------------------------------------------------
# core attention math
# ---------------------------------------------------------------------------

def _expand_kv(k: jnp.ndarray, groups: int) -> jnp.ndarray:
    """(B, T, KV, hd) -> (B, T, KV*groups, hd) by repetition (GQA)."""
    if groups == 1:
        return k
    return jnp.repeat(k, groups, axis=2)


def _attend(q, k, v, *, causal: bool, window: Optional[int],
            q_offset, kv_len: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """q: (B,Sq,H,hd); k,v: (B,T,H,hd).  Masked softmax attention.

    q_offset: absolute position of q[0] minus position of k[0] (so
    query i attends keys j with j <= i + q_offset, and, with a window,
    j > i + q_offset - window).
    kv_len: optional valid length of k/v (ring-buffer decode).
    """
    B, Sq, H, hd = q.shape
    T = k.shape[1]
    scale = 1.0 / np.sqrt(hd)
    if ATTEND_BF16:
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                            preferred_element_type=jnp.float32) * scale
    else:
        scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                            k.astype(jnp.float32)) * scale
    qi = jnp.arange(Sq)[:, None] + q_offset          # absolute q index
    kj = jnp.arange(T)[None, :]
    mask = jnp.ones((Sq, T), bool)
    if causal:
        mask &= kj <= qi
        if window is not None:
            mask &= kj > qi - window
    if kv_len is not None:
        mask &= kj < kv_len
    scores = jnp.where(mask[None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    if ATTEND_BF16:
        out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(q.dtype), v,
                         preferred_element_type=jnp.float32)
    else:
        out = jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


def _attend_chunked(q, k, v, *, causal: bool, window: Optional[int],
                    chunk: int = 0) -> jnp.ndarray:
    """Same as _attend (q_offset=0) but scanned over query chunks so the
    (S, S) score matrix never materializes."""
    chunk = chunk or Q_CHUNK      # module global: §Perf --q-chunk knob
    B, S, H, hd = q.shape
    pad = (-S) % chunk
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    n_chunks = qp.shape[1] // chunk
    qs = qp.reshape(B, n_chunks, chunk, H, hd).transpose(1, 0, 2, 3, 4)

    def body(_, args):
        i, qc = args
        out = _attend(qc, k, v, causal=causal, window=window,
                      q_offset=i * chunk)
        return None, out

    _, outs = jax.lax.scan(body, None, (jnp.arange(n_chunks), qs))
    vd = outs.shape[-1]          # value head dim (MLA: != q head dim)
    out = outs.transpose(1, 0, 2, 3, 4).reshape(B, -1, H, vd)
    return out[:, :S]


# ---------------------------------------------------------------------------
# self-attention: train / prefill / decode
# ---------------------------------------------------------------------------

def make_kv_cache(cfg: ModelConfig, batch: int, max_len: int,
                  window: Optional[int]) -> dict:
    """Allocate an empty cache.  Windowed caches are ring buffers of
    `window` slots; full caches hold max_len slots."""
    if cfg.mla is not None:
        m = cfg.mla
        slots = min(window, max_len) if window else max_len
        return {
            "ckv": jnp.zeros((batch, slots, m.kv_lora_rank), cfg.dtype),
            "krope": jnp.zeros((batch, slots, m.rope_head_dim), cfg.dtype),
            "pos": jnp.zeros((), jnp.int32),
        }
    KV, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    slots = min(window, max_len) if window else max_len
    return {
        "k": jnp.zeros((batch, slots, KV, hd), cfg.dtype),
        "v": jnp.zeros((batch, slots, KV, hd), cfg.dtype),
        "pos": jnp.zeros((), jnp.int32),
    }


def apply_self_attention(p: dict, x: jnp.ndarray, cfg: ModelConfig, *,
                         window: Optional[int],
                         cache: Optional[dict] = None,
                         positions: Optional[jnp.ndarray] = None):
    """Returns (y, new_cache).  cache=None -> train (no cache out).
    x: (B, S, d).  S>1 with cache -> prefill (fills cache);
    S==1 with cache -> single-token decode."""
    if cfg.mla is not None:
        return _apply_mla(p, x, cfg, window=window, cache=cache,
                          positions=positions)
    B, S, d = x.shape
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    groups = H // KV
    if positions is None:
        base = cache["pos"] if cache is not None else 0
        positions = base + jnp.arange(S)[None, :]

    q = dense_apply(p["wq"], x).reshape(B, S, H, hd)
    k = dense_apply(p["wk"], x).reshape(B, S, KV, hd)
    v = dense_apply(p["wv"], x).reshape(B, S, KV, hd)
    if cfg.qk_norm:
        q = norm_apply(p["qnorm"], q)
        k = norm_apply(p["knorm"], k)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)

    if cache is None or S > 1:
        kf = _expand_kv(k, groups)
        vf = _expand_kv(v, groups)
        if S > CHUNK_THRESHOLD:
            out = _attend_chunked(q, kf, vf, causal=True, window=window)
        else:
            out = _attend(q, kf, vf, causal=True, window=window, q_offset=0)
        new_cache = None
        if cache is not None:       # prefill: persist the (ring) tail
            new_cache = _fill_cache(cache, k, v, S)
    else:
        new_cache = _append_cache(cache, k, v)
        kv_len = jnp.minimum(new_cache["pos"], new_cache["k"].shape[1])
        kf = _expand_kv(new_cache["k"], groups)
        vf = _expand_kv(new_cache["v"], groups)
        # ring buffer: score with true positions unnecessary — softmax is
        # permutation-invariant given the validity mask; window recency
        # is enforced by buffer size.
        out = _attend(q, kf, vf, causal=False, window=None,
                      q_offset=0, kv_len=kv_len)
    y = dense_apply(p["wo"], out.reshape(B, S, H * hd))
    return y, new_cache


def _fill_cache(cache: dict, k, v, S: int) -> dict:
    """Prefill: write the last `slots` keys/values into the ring buffer,
    aligned so absolute position p occupies slot p % slots (decode then
    continues the ring seamlessly).  pos records the absolute count."""
    slots = cache["k"].shape[1]
    take = min(S, slots)
    kt = k[:, S - take:]
    vt = v[:, S - take:]
    if take == slots and S % slots:
        kt = jnp.roll(kt, S % slots, axis=1)
        vt = jnp.roll(vt, S % slots, axis=1)
    newk = jax.lax.dynamic_update_slice(
        cache["k"], kt.astype(cache["k"].dtype), (0, 0, 0, 0))
    newv = jax.lax.dynamic_update_slice(
        cache["v"], vt.astype(cache["v"].dtype), (0, 0, 0, 0))
    return {"k": newk, "v": newv, "pos": jnp.asarray(S, jnp.int32)}


def _append_cache(cache: dict, k, v) -> dict:
    """Decode: write one token at pos % slots (ring)."""
    slots = cache["k"].shape[1]
    idx = cache["pos"] % slots
    newk = jax.lax.dynamic_update_slice(
        cache["k"], k.astype(cache["k"].dtype), (0, idx, 0, 0))
    newv = jax.lax.dynamic_update_slice(
        cache["v"], v.astype(cache["v"].dtype), (0, idx, 0, 0))
    return {"k": newk, "v": newv, "pos": cache["pos"] + 1}


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2)
# ---------------------------------------------------------------------------

def _latent_attend(q_lat, q_rope, ckv, krope, *, scale: float,
                   causal: bool, window: Optional[int], q_offset,
                   kv_len: Optional[jnp.ndarray]) -> jnp.ndarray:
    """Absorbed-MLA attention: scores in the latent space, K/V never
    expanded per head.  q_lat: (B,Sq,H,r), q_rope: (B,Sq,H,rd),
    ckv: (B,T,r), krope: (B,T,rd).  Returns out_lat (B,Sq,H,r)."""
    B, Sq, H, r = q_lat.shape
    T = ckv.shape[1]
    scores = (jnp.einsum("bqhr,bkr->bhqk", q_lat.astype(jnp.float32),
                         ckv.astype(jnp.float32))
              + jnp.einsum("bqhd,bkd->bhqk", q_rope.astype(jnp.float32),
                           krope.astype(jnp.float32))) * scale
    qi = jnp.arange(Sq)[:, None] + q_offset
    kj = jnp.arange(T)[None, :]
    mask = jnp.ones((Sq, T), bool)
    if causal:
        mask &= kj <= qi
        if window is not None:
            mask &= kj > qi - window
    if kv_len is not None:
        mask &= kj < kv_len
    scores = jnp.where(mask[None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkr->bqhr", probs, ckv.astype(jnp.float32))
    return out.astype(q_lat.dtype)


def _latent_attend_chunked(q_lat, q_rope, ckv, krope, *, scale, causal,
                           window, chunk: int = 0) -> jnp.ndarray:
    chunk = chunk or Q_CHUNK      # module global: §Perf --q-chunk knob
    B, S, H, r = q_lat.shape
    pad = (-S) % chunk
    zp = lambda x: jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
    ql, qr = zp(q_lat), zp(q_rope)
    n = ql.shape[1] // chunk
    qls = ql.reshape(B, n, chunk, H, r).transpose(1, 0, 2, 3, 4)
    qrs = qr.reshape(B, n, chunk, H, -1).transpose(1, 0, 2, 3, 4)

    def body(_, xs):
        i, qc, qrc = xs
        return None, _latent_attend(qc, qrc, ckv, krope, scale=scale,
                                    causal=causal, window=window,
                                    q_offset=i * chunk, kv_len=None)

    _, outs = jax.lax.scan(body, None, (jnp.arange(n), qls, qrs))
    out = outs.transpose(1, 0, 2, 3, 4).reshape(B, -1, H, r)
    return out[:, :S]

def _apply_mla(p: dict, x: jnp.ndarray, cfg: ModelConfig, *,
               window: Optional[int], cache: Optional[dict],
               positions: Optional[jnp.ndarray]):
    m: MLAConfig = cfg.mla
    B, S, d = x.shape
    H = cfg.num_heads
    nd, rd, vd = m.nope_head_dim, m.rope_head_dim, m.v_head_dim
    if positions is None:
        base = cache["pos"] if cache is not None else 0
        positions = base + jnp.arange(S)[None, :]

    # queries: low-rank then up, or one projection (q_lora_rank=None)
    if "w_q" in p:
        q = dense_apply(p["w_q"], x)
    else:
        q = dense_apply(p["w_uq"],
                        norm_apply(p["q_norm"], dense_apply(p["w_dq"], x)))
    q = q.reshape(B, S, H, nd + rd)
    q_nope, q_rope = q[..., :nd], q[..., nd:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)

    # latent kv + shared rope key
    ckv = norm_apply(p["kv_norm"], dense_apply(p["w_dkv"], x))  # (B,S,r)
    krope = apply_rope(
        dense_apply(p["w_kr"], x).reshape(B, S, 1, rd),
        positions, cfg.rope_theta,
    )[:, :, 0]                                                   # (B,S,rd)

    kv_len = None
    if cache is not None and S == 1:
        slots = cache["ckv"].shape[1]
        idx = cache["pos"] % slots
        cache = {
            "ckv": jax.lax.dynamic_update_slice(
                cache["ckv"], ckv.astype(cache["ckv"].dtype), (0, idx, 0)),
            "krope": jax.lax.dynamic_update_slice(
                cache["krope"], krope.astype(cache["krope"].dtype),
                (0, idx, 0)),
            "pos": cache["pos"] + 1,
        }
        ckv_all, krope_all = cache["ckv"], cache["krope"]
        kv_len = jnp.minimum(cache["pos"], slots)
        causal = False
    else:
        ckv_all, krope_all = ckv, krope
        causal = True

    T = ckv_all.shape[1]
    scale = 1.0 / np.sqrt(nd + rd)
    if m.absorbed:
        # score & combine in latent space: K/V never expand to
        # (B, T, H, nd) — trades latent-rank score FLOPs for H× less
        # HBM traffic (the memory-bound §Perf variant).
        r = m.kv_lora_rank
        w_uk = p["w_uk"]["w"].reshape(r, H, nd)
        q_lat = jnp.einsum("bqhd,rhd->bqhr", q_nope, w_uk)
        if causal and S > CHUNK_THRESHOLD:
            out_lat = _latent_attend_chunked(
                q_lat, q_rope, ckv_all, krope_all, scale=scale,
                causal=True, window=window)
        else:
            out_lat = _latent_attend(
                q_lat, q_rope, ckv_all, krope_all, scale=scale,
                causal=causal, window=window if causal else None,
                q_offset=0, kv_len=kv_len)
        w_uv = p["w_uv"]["w"].reshape(r, H, vd)
        out = jnp.einsum("bqhr,rhv->bqhv", out_lat, w_uv)
    else:
        k_nope = dense_apply(p["w_uk"], ckv_all).reshape(B, T, H, nd)
        vv = dense_apply(p["w_uv"], ckv_all).reshape(B, T, H, vd)
        k_rope_b = jnp.broadcast_to(krope_all[:, :, None, :],
                                    (B, T, H, rd))
        k_full = jnp.concatenate([k_nope, k_rope_b], axis=-1)
        q_full = jnp.concatenate([q_nope, q_rope], axis=-1)

        if causal and S > CHUNK_THRESHOLD:
            out = _attend_chunked(q_full, k_full, vv, causal=True,
                                  window=window)
        else:
            out = _attend(q_full, k_full, vv, causal=causal,
                          window=window if causal else None,
                          q_offset=0, kv_len=kv_len)
    y = dense_apply(p["wo"], out.reshape(B, S, H * vd))

    new_cache = cache
    if cache is not None and S > 1:   # prefill fill (ring-aligned)
        slots = cache["ckv"].shape[1]
        take = min(S, slots)
        ct = ckv[:, S - take:]
        rt = krope[:, S - take:]
        if take == slots and S % slots:
            ct = jnp.roll(ct, S % slots, axis=1)
            rt = jnp.roll(rt, S % slots, axis=1)
        new_cache = {
            "ckv": jax.lax.dynamic_update_slice(
                cache["ckv"], ct.astype(cache["ckv"].dtype), (0, 0, 0)),
            "krope": jax.lax.dynamic_update_slice(
                cache["krope"], rt.astype(cache["krope"].dtype), (0, 0, 0)),
            "pos": jnp.asarray(S, jnp.int32),
        }
    return y, new_cache


# ---------------------------------------------------------------------------
# cross-attention (VLM image layers, enc-dec decoder)
# ---------------------------------------------------------------------------

def precompute_cross_kv(p: dict, memory: jnp.ndarray, cfg: ModelConfig
                        ) -> dict:
    """Project encoder/frontend memory to K/V once (reused every step)."""
    B, M, _ = memory.shape
    KV, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    return {
        "k": dense_apply(p["wk"], memory).reshape(B, M, KV, hd),
        "v": dense_apply(p["wv"], memory).reshape(B, M, KV, hd),
    }


def apply_cross_attention(p: dict, x: jnp.ndarray, cfg: ModelConfig, *,
                          memory: Optional[jnp.ndarray] = None,
                          mem_kv: Optional[dict] = None) -> jnp.ndarray:
    B, S, d = x.shape
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    groups = H // KV
    if mem_kv is None:
        mem_kv = precompute_cross_kv(p, memory, cfg)
    q = dense_apply(p["wq"], x).reshape(B, S, H, hd)
    kf = _expand_kv(mem_kv["k"], groups)
    vf = _expand_kv(mem_kv["v"], groups)
    out = _attend(q, kf, vf, causal=False, window=None, q_offset=0)
    return dense_apply(p["wo"], out.reshape(B, S, H * hd))
