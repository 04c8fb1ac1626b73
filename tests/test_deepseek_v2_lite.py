"""DeepSeek-V2-Lite's mechanisms against plain float32 ``jax.numpy``
references written from the published equations (arXiv:2405.04434 §2.1,
§2.2; the model's config.json), at a small size on the CPU:

* MLA without query compression (``q_lora_rank=None``): q = W^Q h.
* Routed experts with softmax scores that are not renormalized
  (``norm_topk_prob=False``) plus 2 shared experts.
* One expert-parallel rank's share: the 8 shares' parts, with the shared
  experts counted once, add up to the uncut layer.

Departures of the repo's model, noted: RoPE rotates the halves of the
rope dims (the published code de-interleaves before the same rotation;
with seeded weights that is a fixed permutation of the rope columns),
and YaRN's long-context scaling is not applied.

Tolerances: the references sum in another order than the model's
einsums (the MoE combine sums all experts' slots, the shares sum their
parts), so float32 results differ by round-off: rtol 1e-5 and atol 1e-6
at these sizes (unit-scale activations), while one bfloat16 rounding of
any operand moves them by about 1e-3.
"""
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import deepseek_v2_lite as lite
from repro.models import attention as attn
from repro.models import moe
from repro.models import transformer as tf
from repro.models.config import MLAConfig, ModelConfig, MoEConfig

ROOT = pathlib.Path(__file__).resolve().parent.parent
RTOL, ATOL = 1e-5, 1e-6


def _cfg(**moe_kw):
    return ModelConfig(
        name="lite-tiny", arch_type="moe", num_layers=2, d_model=64,
        num_heads=4, num_kv_heads=4, d_ff=96, vocab_size=128,
        prefix_kinds=("dense",), scan_pattern=("moe",), dtype=jnp.float32,
        mla=MLAConfig(kv_lora_rank=16, q_lora_rank=None, nope_head_dim=8,
                      rope_head_dim=4, v_head_dim=8),
        moe=MoEConfig(num_experts=16, top_k=6, d_ff_expert=8,
                      num_shared_experts=2, d_ff_residual=8,
                      norm_topk_prob=False, capacity_factor=16.0, **moe_kw),
        vocab_pad_multiple=16)


def _x(shape, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)


def test_published_values():
    c = lite.get_config()
    assert (c.num_layers, c.d_model, c.num_heads, c.d_ff, c.vocab_size) == \
        (27, 2048, 16, 10944, 102400)
    assert c.prefix_kinds == ("dense",) and not c.tie_embeddings
    assert (c.mla.q_lora_rank, c.mla.kv_lora_rank, c.mla.nope_head_dim,
            c.mla.rope_head_dim, c.mla.v_head_dim) == (None, 512, 128, 64, 128)
    m = c.moe
    assert (m.num_experts, m.top_k, m.d_ff_expert, m.num_shared_experts,
            m.d_ff_residual, m.norm_topk_prob, m.held_experts) == \
        (64, 6, 1408, 2, 1408, False, None)


def test_share_keeps_every_width():
    full, share = lite.get_config(), lite.share_config()
    assert share.num_layers == 5 and share.num_heads == 2
    assert share.vocab_size == 12800 and share.padded_vocab == 12800
    assert share.moe.held == tuple(range(8))
    assert share.moe.num_experts == 64 and share.moe.top_k == 6
    for k in ("d_model", "d_ff", "mla"):
        assert getattr(share, k) == getattr(full, k)
    assert (share.moe.d_ff_expert, share.moe.num_shared_experts,
            share.moe.d_ff_residual) == (1408, 2, 1408)


def test_benchmark_leaves_are_the_share_models_own():
    doc = json.loads((ROOT / "chipbench" / "configs"
                      / "xsilo-deepseek-v2-lite-ep8.json").read_text())
    shapes = jax.eval_shape(lambda k: tf.init_lm(k, lite.share_config()),
                            jax.random.PRNGKey(0))
    want = [[".".join(str(getattr(p, "key", getattr(p, "idx", p)))
                      for p in path), list(x.shape), str(x.dtype)]
            for path, x in jax.tree_util.tree_flatten_with_path(shapes)[0]]
    assert doc["update"]["leaves"] == want
    assert doc["experts"]["grid"] == [4, 8]
    for name in doc["experts"]["leaves"]:
        assert dict((n, s) for n, s, _ in want)[name][:2] == [4, 8]


# -- MLA without query compression -------------------------------------------

def _rope(x, pos, theta):
    """x (..., S, H, D): rotate pairs (i, i + D/2) by pos·theta^(-2i/D)."""
    d = x.shape[-1]
    f = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos[:, None] * f                              # (S, D/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _rms(x, scale, eps=1e-6):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _mla_reference(p, h, cfg):
    m, H = cfg.mla, cfg.num_heads
    B, S, _ = h.shape
    nd, rd, vd = m.nope_head_dim, m.rope_head_dim, m.v_head_dim
    pos = jnp.arange(S, dtype=jnp.float32)
    q = (h @ p["w_q"]["w"]).reshape(B, S, H, nd + rd)
    q_c, q_r = q[..., :nd], _rope(q[..., nd:], pos, cfg.rope_theta)
    c_kv = _rms(h @ p["w_dkv"]["w"], p["kv_norm"]["scale"])
    k_c = (c_kv @ p["w_uk"]["w"]).reshape(B, S, H, nd)
    v = (c_kv @ p["w_uv"]["w"]).reshape(B, S, H, vd)
    k_r = _rope((h @ p["w_kr"]["w"])[:, :, None, :], pos, cfg.rope_theta)
    scores = (jnp.einsum("bqhd,bkhd->bhqk", q_c, k_c)
              + jnp.einsum("bqhd,bkd->bhqk", q_r, k_r[:, :, 0])) \
        / np.sqrt(nd + rd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    a = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
    o = jnp.einsum("bhqk,bkhd->bqhd", a, v).reshape(B, S, H * vd)
    return o @ p["wo"]["w"]


def test_mla_init_has_one_query_projection():
    cfg = _cfg()
    p = attn.init_self_attention(jax.random.PRNGKey(0), cfg)
    assert "w_q" in p and not {"w_dq", "q_norm", "w_uq"} & set(p)
    assert p["w_q"]["w"].shape == (64, 4 * (8 + 4))


@pytest.mark.parametrize("absorbed", [False, True])
def test_mla_without_query_compression_matches_reference(absorbed):
    cfg = _cfg()
    cfg = cfg.with_overrides(mla=MLAConfig(**{**cfg.mla.__dict__,
                                              "absorbed": absorbed}))
    p = attn.init_self_attention(jax.random.PRNGKey(0), cfg)
    h = _x((2, 10, 64), 1)
    with jax.default_matmul_precision("highest"):
        y, _ = attn.apply_self_attention(p, h, cfg, window=None)
        ref = _mla_reference(p, h, cfg)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               rtol=RTOL, atol=ATOL)


# -- routed + shared experts -------------------------------------------------

def _ffn(x, g, u, d):
    return (jax.nn.silu(x @ g) * (x @ u)) @ d


def _moe_reference(p, x, cfg):
    mc = cfg.moe
    xt = x.reshape(-1, x.shape[-1])
    s = jax.nn.softmax(xt @ p["router"]["w"], -1)            # (T, E)
    top = jnp.argsort(-s, -1)[:, :mc.top_k]
    y = jnp.zeros_like(xt)
    for t in range(xt.shape[0]):
        for e in np.asarray(top[t]):
            y = y.at[t].add(s[t, e] * _ffn(xt[t], p["w_gate"][e],
                                           p["w_up"][e], p["w_down"][e]))
    sh = p["shared"]
    y = y + _ffn(xt, sh["gate"]["w"], sh["up"]["w"], sh["down"]["w"])
    return y.reshape(x.shape)


def test_moe_unnormalized_gates_and_shared_experts_match_reference():
    cfg = _cfg()
    p = moe.init_moe(jax.random.PRNGKey(2), cfg)
    assert p["shared"]["gate"]["w"].shape == (64, 2 * 8)
    x = _x((2, 6, 64), 3)
    with jax.default_matmul_precision("highest"):
        y, _ = moe.apply_moe(p, x, cfg)
        ref = _moe_reference(p, x, cfg)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               rtol=RTOL, atol=ATOL)


def test_normalized_gates_differ():
    """norm_topk_prob=True (the other configs' routing) is another layer."""
    cfg = _cfg()
    p = moe.init_moe(jax.random.PRNGKey(2), cfg)
    x = _x((2, 6, 64), 3)
    normed = cfg.with_overrides(moe=MoEConfig(**{**cfg.moe.__dict__,
                                                 "norm_topk_prob": True}))
    with jax.default_matmul_precision("highest"):
        y, _ = moe.apply_moe(p, x, cfg)
        y_n, _ = moe.apply_moe(p, x, normed)
    assert not np.allclose(np.asarray(y), np.asarray(y_n), rtol=1e-3)


def test_eight_expert_shares_add_up_to_the_uncut_layer():
    """Each rank holds 2 of 16 experts and routes over all 16; the shared
    experts, which every rank computes alike, are counted once."""
    cfg = _cfg()
    p = moe.init_moe(jax.random.PRNGKey(4), cfg)
    x = _x((2, 8, 64), 5)
    total, auxes = 0.0, []
    with jax.default_matmul_precision("highest"):
        whole, aux = moe.apply_moe(p, x, cfg)
        for r in range(8):
            held = (2 * r, 2 * r + 1)
            c = cfg.with_overrides(moe=MoEConfig(**{**cfg.moe.__dict__,
                                                    "held_experts": held}))
            pr = {"router": p["router"],
                  **{k: p[k][np.asarray(held)]
                     for k in ("w_gate", "w_up", "w_down")}}
            if r == 0:
                pr["shared"] = p["shared"]
            y, a = moe.apply_moe(pr, x, c)
            total = total + y
            auxes.append(float(a))
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=RTOL, atol=ATOL)
    assert auxes == [float(aux)] * 8


def test_share_init_holds_only_its_experts():
    c = _cfg(held_experts=(4, 5, 6))
    p = moe.init_moe(jax.random.PRNGKey(0), c)
    assert p["w_gate"].shape == (3, 64, 8)
    assert p["router"]["w"].shape == (64, 16)


def test_lite_share_forward_runs():
    """The reduced Lite model (q_lora_rank None, unnormalized gates, 2
    shared experts) trains a step and decodes through its cache."""
    cfg = lite.reduced_config()
    params = tf.init_lm(jax.random.PRNGKey(0), cfg)
    assert "w_q" in params["decoder"]["prefix"][0]["attn"]
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                             cfg.vocab_size)
    loss, _ = tf.lm_loss(params, {"tokens": tok, "labels": tok}, cfg,
                         remat=False)
    assert bool(jnp.isfinite(loss))
