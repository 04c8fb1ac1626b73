"""Mixture-of-Experts: token-choice top-k routing, GShard-style
capacity dispatch (einsum one-hot — expert-parallel friendly), shared
experts (DeepSeek-V2) and a parallel dense residual MLP (Arctic).

Tokens are routed in groups of ~TARGET_GROUP (GShard's standard trick):
the (tokens, experts, capacity) dispatch tensor exists only per group,
scanned over the sequence, so its footprint is bounded regardless of
batch x seq.  Expert FLOPs scale with routed capacity, NOT with E —
compiled FLOPs stay honest for the roofline table.

Expert weights carry a leading E dim that shards over the `model` mesh
axis (expert parallelism); XLA SPMD lowers dispatch/combine into
all-to-alls / reduce-scatters on that axis.  A layer told which experts
it holds (`MoEConfig.held_experts`, one rank's share) keeps only those
experts' weights and adds only their part of the routed output; the
router still scores, and capacity still counts, all experts.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from .config import ModelConfig, MoEConfig
from .layers import dense_apply, dense_init, mlp_apply, mlp_init

TARGET_GROUP = 8192    # tokens routed together (global)

# §Perf knob: PartitionSpec for the dispatched expert activations
# xe/h/ye (E, C, d|ff).  None = let SPMD choose (baseline).  Setting
# ("model", "data", None) forces the capacity dim onto the data axis so
# the dispatch contraction lowers as reduce-scatter instead of
# all-reduce (launch/dryrun --moe-act-shard).
MOE_ACT_SPEC = None


def set_moe_act_spec(spec) -> None:
    globals()["MOE_ACT_SPEC"] = spec


def _constrain(x):
    if MOE_ACT_SPEC is None:
        return x
    from jax.sharding import PartitionSpec as P
    spec = P(*MOE_ACT_SPEC[: x.ndim])
    try:
        return jax.lax.with_sharding_constraint(x, spec)
    except Exception:
        return x


def init_moe(key, cfg: ModelConfig) -> dict:
    mc: MoEConfig = cfg.moe
    d, ff, E = cfg.d_model, mc.d_ff_expert, mc.num_experts
    H = len(mc.held)            # expert leaves hold this rank's experts
    ks = jax.random.split(key, 6)
    scale = 1.0 / np.sqrt(d)
    p = {
        "router": dense_init(ks[0], d, E, scale=0.02, dtype=jnp.float32),
        "w_gate": (jax.random.normal(ks[1], (H, d, ff), jnp.float32)
                   * scale).astype(cfg.dtype),
        "w_up": (jax.random.normal(ks[2], (H, d, ff), jnp.float32)
                 * scale).astype(cfg.dtype),
        "w_down": (jax.random.normal(ks[3], (H, ff, d), jnp.float32)
                   / np.sqrt(ff)).astype(cfg.dtype),
    }
    if mc.num_shared_experts > 0:
        shared_ff = mc.num_shared_experts * (mc.d_ff_residual or ff)
        p["shared"] = mlp_init(ks[4], d, shared_ff, cfg.act, cfg.dtype)
    if mc.dense_residual:
        res_ff = mc.d_ff_residual or ff
        p["residual"] = mlp_init(ks[5], d, res_ff, cfg.act, cfg.dtype)
    return p


def _capacity(T: int, E: int, top_k: int, factor: float) -> int:
    return max(1, int(math.ceil(T * top_k / E * factor)))


def _route_group(p: dict, xt: jnp.ndarray, cfg: ModelConfig
                 ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Route one token group.  xt: (T, d) -> (y: (T, d), aux scalar)."""
    mc: MoEConfig = cfg.moe
    T, d = xt.shape
    E, k = mc.num_experts, mc.top_k
    C = _capacity(T, E, k, mc.capacity_factor)

    logits = dense_apply(p["router"], xt.astype(jnp.float32))    # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)

    gate_vals, gate_idx = jax.lax.top_k(probs, k)                # (T, k)
    if mc.norm_topk_prob:
        gate_vals = gate_vals / (gate_vals.sum(-1, keepdims=True) + 1e-9)

    onehot = jax.nn.one_hot(gate_idx, E, dtype=jnp.float32)      # (T,k,E)
    # position of each (token, choice) within its expert's capacity
    flat = onehot.reshape(T * k, E)
    pos = jnp.cumsum(flat, axis=0) - flat
    pos = (pos * flat).sum(-1).reshape(T, k)                     # (T, k)
    keep = pos < C
    gate_vals = gate_vals * keep

    pos_onehot = jax.nn.one_hot(pos, C, dtype=jnp.float32)       # (T,k,C)
    disp = jnp.einsum("tke,tkc->tec", onehot * keep[..., None],
                      pos_onehot)
    comb = jnp.einsum("tke,tkc,tk->tec", onehot, pos_onehot, gate_vals)
    if mc.held_experts is not None:
        # routed over all E; only the held experts' part is computed
        held = jnp.asarray(mc.held, jnp.int32)
        disp, comb = disp[:, held], comb[:, held]

    xe = _constrain(
        jnp.einsum("tec,td->ecd", disp.astype(cfg.dtype), xt))   # (E,C,d)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, p["w_gate"])) \
        * jnp.einsum("ecd,edf->ecf", xe, p["w_up"])
    ye = _constrain(
        jnp.einsum("ecf,efd->ecd", _constrain(h), p["w_down"]))  # (E,C,d)
    y = jnp.einsum("tec,ecd->td", comb.astype(cfg.dtype), ye)    # (T,d)

    # load-balance auxiliary loss (Switch/GShard style)
    frac_tokens = onehot[:, 0].mean(axis=0)
    frac_probs = probs.mean(axis=0)
    aux = E * jnp.sum(frac_tokens * frac_probs) * mc.router_aux_weight
    return y, aux


def apply_moe(p: dict, x: jnp.ndarray, cfg: ModelConfig
              ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, d) -> (y, aux_loss).  Groups of <=TARGET_GROUP tokens
    are routed per lax.scan step (sequence-chunked)."""
    B, S, d = x.shape
    chunk_s = max(1, min(S, TARGET_GROUP // B))
    while S % chunk_s:
        chunk_s -= 1          # shapes here are powers of two; loop is cheap
    n_chunks = S // chunk_s

    if n_chunks == 1:
        y, aux = _route_group(p, x.reshape(B * S, d), cfg)
        out = y.reshape(B, S, d)
    else:
        xs = x.reshape(B, n_chunks, chunk_s, d).transpose(1, 0, 2, 3)

        def body(_, xc):
            yc, aux_c = _route_group(p, xc.reshape(B * chunk_s, d), cfg)
            return None, (yc.reshape(B, chunk_s, d), aux_c)

        _, (ys, auxs) = jax.lax.scan(body, None, xs)
        out = ys.transpose(1, 0, 2, 3).reshape(B, S, d)
        aux = jnp.mean(auxs)

    if "shared" in p:
        out = out + mlp_apply(p["shared"], x, cfg.act)
    if "residual" in p:
        out = out + mlp_apply(p["residual"], x, cfg.act)
    return out, aux
