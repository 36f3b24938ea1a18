"""Model layers: norms, RoPE, blockwise attention (GQA), MLA, MoE, Mamba2.

Conventions
-----------
* Every layer exposes ``*_meta(cfg) -> meta tree`` (ParamMeta leaves) and
  ``*_apply(params, ...)`` / ``*_decode(params, cache, ...)`` functions.
* Activations: (B, S, d_model); compute in the config dtype, reductions and
  softmax in f32.
* Long sequences never materialize (S, S): attention uses a nested
  q-block/kv-block online-softmax scan (the pure-jnp twin of the Pallas
  flash kernel in ``repro.kernels.flash_attention``; on real TPU the kernel
  substitutes via the ``use_flash_kernel`` flag).
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.config import ModelConfig
from repro.models.meta import ParamMeta

Params = Any
F32 = jnp.float32


# ---------------------------------------------------------------------------
# Norm / RoPE
# ---------------------------------------------------------------------------
def rmsnorm_meta(d: int) -> ParamMeta:
    return ParamMeta((d,), ("embed",), init="ones")


def rmsnorm(x, w, eps: float = 1e-6):
    xf = x.astype(F32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w.astype(x.dtype)


def rope_freqs(d_head: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, d_head, 2, dtype=F32) / d_head))


def apply_rope(x, positions, theta: float):
    """x: (B, S, H, D); positions: (B, S) or (S,)."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                      # (D/2,)
    ang = positions.astype(F32)[..., None] * freqs    # (B, S, D/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    cos = cos[..., None, :]                           # (B, S, 1, D/2)
    sin = sin[..., None, :]
    x1, x2 = x[..., 0::2].astype(F32), x[..., 1::2].astype(F32)
    o1 = x1 * cos - x2 * sin
    o2 = x2 * cos + x1 * sin
    out = jnp.stack([o1, o2], axis=-1).reshape(x.shape)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Blockwise attention (pure-jnp flash twin)
# ---------------------------------------------------------------------------
NEG_INF = -1e30


def _attn_block(q, k, v, m, l, acc, causal_mask):
    """One online-softmax update. q: (B, bq, H, D); k/v: (B, bk, Kh, D)."""
    b, bq, h, d = q.shape
    kh = k.shape[2]
    g = h // kh
    qg = q.reshape(b, bq, kh, g, d)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg.astype(F32), k.astype(F32))
    s = s * (d ** -0.5)
    if causal_mask is not None:
        s = jnp.where(causal_mask[None, None, None], s, NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))       # (B,Kh,G,bq)
    p = jnp.exp(s - m_new[..., None])
    alpha = jnp.exp(m - m_new)
    l_new = alpha * l + jnp.sum(p, axis=-1)
    pv = jnp.einsum("bkgqs,bskd->bkgqd", p, v.astype(F32))
    acc_new = acc * alpha[..., None] + pv
    return m_new, l_new, acc_new


def blockwise_attention(q, k, v, *, causal: bool, block: int = 512,
                        q_offset=0):
    """q: (B, Sq, H, D); k/v: (B, Skv, Kh, D) -> (B, Sq, H, D).

    Nested scan: outer over q blocks, inner over kv blocks, carrying the
    online-softmax state; score blocks are (B, Kh, G, bq, bk). Sequences are
    padded internally to whole blocks (padded KV positions are masked out).
    """
    b, sq0, h, d = q.shape
    skv0, kh = k.shape[1], k.shape[2]
    bq = min(block, sq0)
    bk = min(block, skv0)

    def _pad_seq(x, mult):
        pad = (-x.shape[1]) % mult
        if pad == 0:
            return x
        widths = [(0, 0)] * x.ndim
        widths[1] = (0, pad)
        return jnp.pad(x, widths)

    q = _pad_seq(q, bq)
    k = _pad_seq(k, bk)
    v = _pad_seq(v, bk)
    sq, skv = q.shape[1], k.shape[1]
    kv_valid = skv0
    nq, nk = sq // bq, skv // bk
    g = h // kh
    dv = v.shape[-1]                                   # may differ (MLA)

    k_blocks = k.reshape(b, nk, bk, kh, d).swapaxes(0, 1)  # (nk,B,bk,Kh,D)
    v_blocks = v.reshape(b, nk, bk, kh, dv).swapaxes(0, 1)
    q_blocks = q.reshape(b, nq, bq, h, d).swapaxes(0, 1)

    # NOTE 1: block positions are threaded through the scan CARRIES (not
    # taken from iota xs): index-only quantities get loop-hoisted by XLA
    # into an (nq x nk x bq x bk) precomputed mask stack — 2 GiB at 32k.
    # Carry-dependence keeps the (bq, bk) mask transient per iteration.
    # NOTE 2: the inner body is jax.checkpoint'ed: without it, reverse-mode
    # saves every block's (bq, bk) scores/probabilities across all nq x nk
    # iterations — the full S^2 flash attention is meant to avoid. Remat
    # recomputes each block's scores in its own backward (flash-bwd style).
    def outer(q_base, qb):
        q_pos = q_offset + q_base + jnp.arange(bq)

        @jax.checkpoint
        def inner(carry, kb_vb):
            m, l, acc, k_base = carry
            kb, vb = kb_vb
            k_pos = k_base + jnp.arange(bk)
            mask = (k_pos < kv_valid)[None, :]
            if causal:
                mask = mask & (q_pos[:, None] >= k_pos[None, :])
            else:
                mask = jnp.broadcast_to(mask, (bq, bk))
            m, l, acc = _attn_block(qb, kb, vb, m, l, acc, mask)
            return (m, l, acc, k_base + bk), None

        init = (jnp.full((b, kh, g, bq), NEG_INF, F32),
                jnp.zeros((b, kh, g, bq), F32),
                jnp.zeros((b, kh, g, bq, dv), F32),
                jnp.zeros((), jnp.int32))
        (m, l, acc, _), _ = jax.lax.scan(
            init=init, xs=(k_blocks, v_blocks), f=inner)
        o = acc / jnp.maximum(l, 1e-30)[..., None]     # (B,Kh,G,bq,Dv)
        o = o.transpose(0, 3, 1, 2, 4).reshape(b, bq, h, dv)
        return q_base + bq, o.astype(q.dtype)

    _, outs = jax.lax.scan(outer, jnp.zeros((), jnp.int32), q_blocks)
    return outs.swapaxes(0, 1).reshape(b, sq, h, dv)[:, :sq0]


def decode_attention(q, k_cache, v_cache, kv_len):
    """Single-step attention over a cache. q: (B, 1, H, D);
    k/v_cache: (B, S, Kh, D); kv_len: () valid prefix length."""
    b, _, h, d = q.shape
    s, kh = k_cache.shape[1], k_cache.shape[2]
    g = h // kh
    qg = q.reshape(b, kh, g, d)
    scores = jnp.einsum("bkgd,bskd->bkgs", qg.astype(F32),
                        k_cache.astype(F32)) * (d ** -0.5)
    mask = jnp.arange(s)[None, None, None, :] < kv_len
    scores = jnp.where(mask, scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bkgs,bskd->bkgd", p, v_cache.astype(F32))
    return o.reshape(b, 1, h, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA attention layer
# ---------------------------------------------------------------------------
def attn_meta(cfg: ModelConfig, cross: bool = False) -> dict:
    d, h, kv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv, cfg.d_head
    meta = {
        "wq": ParamMeta((d, h * dh), ("embed", "heads_dh")),
        "wk": ParamMeta((d, kv * dh), ("embed", "kv_dh")),
        "wv": ParamMeta((d, kv * dh), ("embed", "kv_dh")),
        "wo": ParamMeta((h * dh, d), ("heads_dh", "embed")),
        "norm": rmsnorm_meta(d),
    }
    if cfg.qkv_bias and not cross:
        meta["bq"] = ParamMeta((h * dh,), ("heads_dh",), init="zeros")
        meta["bk"] = ParamMeta((kv * dh,), ("kv_dh",), init="zeros")
        meta["bv"] = ParamMeta((kv * dh,), ("kv_dh",), init="zeros")
    return meta


def _qkv(params, x, cfg: ModelConfig, positions=None, rope: bool = True):
    b, s, _ = x.shape
    h, kv, dh = cfg.n_heads, cfg.n_kv, cfg.d_head
    q = x @ params["wq"].astype(x.dtype)
    k = x @ params["wk"].astype(x.dtype)
    v = x @ params["wv"].astype(x.dtype)
    if "bq" in params:
        q = q + params["bq"].astype(x.dtype)
        k = k + params["bk"].astype(x.dtype)
        v = v + params["bv"].astype(x.dtype)
    q = q.reshape(b, s, h, dh)
    k = k.reshape(b, s, kv, dh)
    v = v.reshape(b, s, kv, dh)
    if rope:
        if positions is None:
            positions = jnp.arange(s)[None, :]
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def attn_apply(params, x, cfg: ModelConfig, *, causal: bool = True,
               positions=None):
    """Full-sequence self-attention (train / prefill). Returns (out, (k, v))
    so prefill can seed the decode cache."""
    b, s, _ = x.shape
    xn = rmsnorm(x, params["norm"], cfg.norm_eps)
    q, k, v = _qkv(params, xn, cfg, positions=positions)
    o = blockwise_attention(q, k, v, causal=causal,
                            block=cfg.attention_block)
    o = o.reshape(b, s, cfg.n_heads * cfg.d_head)
    return o @ params["wo"].astype(x.dtype), (k, v)


def quantize_kv(t):
    """(B, S, Kh, Dh) -> (int8 values, f32 per-(B,S,Kh) scales)."""
    absmax = jnp.max(jnp.abs(t.astype(F32)), axis=-1, keepdims=True)
    scale = jnp.maximum(absmax / 127.0, 1e-12)
    q = jnp.clip(jnp.round(t.astype(F32) / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def attn_decode(params, x, cache, cfg: ModelConfig):
    """x: (B, 1, d); cache: {"k","v": (B, Smax, Kh, Dh), "pos": ()}.

    int8-quantized cache variant (a *data encoding* in the paper's sense —
    Section 10 — applied to the KV stream): cache additionally holds
    per-(B, S, Kh) f32 scales as "k_s"/"v_s"; K/V are dequantized into the
    attention in f32. Halves decode HBM cache traffic + capacity vs bf16."""
    b = x.shape[0]
    xn = rmsnorm(x, params["norm"], cfg.norm_eps)
    pos = cache["pos"]
    q, k, v = _qkv(params, xn, cfg,
                   positions=jnp.full((b, 1), pos, dtype=jnp.int32))
    quantized = "k_s" in cache
    if quantized:
        kq, ks = quantize_kv(k)
        vq, vs = quantize_kv(v)
        k_cache = jax.lax.dynamic_update_slice_in_dim(
            cache["k"], kq, pos, axis=1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(
            cache["v"], vq, pos, axis=1)
        ks_cache = jax.lax.dynamic_update_slice_in_dim(
            cache["k_s"], ks, pos, axis=1)
        vs_cache = jax.lax.dynamic_update_slice_in_dim(
            cache["v_s"], vs, pos, axis=1)
        k_full = k_cache.astype(F32) * ks_cache
        v_full = v_cache.astype(F32) * vs_cache
        o = decode_attention(q, k_full, v_full, pos + 1)
        new_cache = {"k": k_cache, "v": v_cache, "k_s": ks_cache,
                     "v_s": vs_cache, "pos": pos + 1}
    else:
        k_cache = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, pos,
                                                      axis=1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, pos,
                                                      axis=1)
        o = decode_attention(q, k_cache, v_cache, pos + 1)
        new_cache = {"k": k_cache, "v": v_cache, "pos": pos + 1}
    o = o.reshape(b, 1, cfg.n_heads * cfg.d_head)
    return o @ params["wo"].astype(x.dtype), new_cache


# ---------------------------------------------------------------------------
# Cross-attention (vision adapters, enc-dec): KV from auxiliary embeddings
# ---------------------------------------------------------------------------
def xattn_apply(params, x, aux_kv, cfg: ModelConfig):
    """aux_kv: precomputed (k, v): (B, S_aux, Kh, Dh)."""
    b, s, _ = x.shape
    xn = rmsnorm(x, params["norm"], cfg.norm_eps)
    h, dh = cfg.n_heads, cfg.d_head
    q = (xn @ params["wq"].astype(x.dtype)).reshape(b, s, h, dh)
    k, v = aux_kv
    o = blockwise_attention(q, k, v, causal=False, block=cfg.attention_block)
    o = o.reshape(b, s, h * dh)
    return o @ params["wo"].astype(x.dtype)


def xattn_kv(params, aux, cfg: ModelConfig):
    """Project auxiliary embeddings once: (B, S_aux, d) -> (k, v)."""
    b, s, _ = aux.shape
    kv, dh = cfg.n_kv, cfg.d_head
    k = (aux @ params["wk"].astype(aux.dtype)).reshape(b, s, kv, dh)
    v = (aux @ params["wv"].astype(aux.dtype)).reshape(b, s, kv, dh)
    return k, v


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2) — compressed KV latent attention
# ---------------------------------------------------------------------------
def mla_meta(cfg: ModelConfig) -> dict:
    m = cfg.mla
    d, h = cfg.d_model, cfg.n_heads
    return {
        "wq": ParamMeta((d, h * (m.d_nope + m.d_rope)), ("embed", "heads_dh")),
        "w_dkv": ParamMeta((d, m.kv_lora), ("embed", None)),
        "w_kr": ParamMeta((d, m.d_rope), ("embed", None)),
        "w_uk": ParamMeta((m.kv_lora, h * m.d_nope), (None, "heads_dh")),
        "w_uv": ParamMeta((m.kv_lora, h * m.d_v), (None, "heads_dh")),
        "wo": ParamMeta((h * m.d_v, d), ("heads_dh", "embed")),
        "norm": rmsnorm_meta(d),
        "kv_norm": ParamMeta((m.kv_lora,), (None,), init="ones"),
    }


def mla_apply(params, x, cfg: ModelConfig, positions=None):
    """Training/prefill MLA: expand K/V from the latent, blockwise attention.
    Returns (out, (c_kv, k_rope)) for cache seeding."""
    m = cfg.mla
    b, s, _ = x.shape
    h = cfg.n_heads
    xn = rmsnorm(x, params["norm"], cfg.norm_eps)
    if positions is None:
        positions = jnp.arange(s)[None, :]

    q = (xn @ params["wq"].astype(x.dtype)).reshape(b, s, h,
                                                    m.d_nope + m.d_rope)
    q_nope, q_rope = q[..., :m.d_nope], q[..., m.d_nope:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)

    c_kv = rmsnorm(xn @ params["w_dkv"].astype(x.dtype), params["kv_norm"],
                   cfg.norm_eps)                       # (B, S, kv_lora)
    k_rope = apply_rope((xn @ params["w_kr"].astype(x.dtype))[:, :, None, :],
                        positions, cfg.rope_theta)     # (B, S, 1, d_rope)
    k_nope = (c_kv @ params["w_uk"].astype(x.dtype)).reshape(
        b, s, h, m.d_nope)
    v = (c_kv @ params["w_uv"].astype(x.dtype)).reshape(b, s, h, m.d_v)

    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        k_rope, (b, s, h, m.d_rope))], axis=-1)
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
    o = blockwise_attention(q_full, k, v, causal=True,
                            block=cfg.attention_block)
    o = o.reshape(b, s, h * m.d_v)
    return o @ params["wo"].astype(x.dtype), (c_kv, k_rope[:, :, 0, :])


def mla_decode(params, x, cache, cfg: ModelConfig):
    """Absorbed-matrix MLA decode: attention runs directly over the latent
    cache (B, S, kv_lora) + shared rope key (B, S, d_rope)."""
    m = cfg.mla
    b = x.shape[0]
    h = cfg.n_heads
    xn = rmsnorm(x, params["norm"], cfg.norm_eps)
    pos = cache["pos"]
    positions = jnp.full((b, 1), pos, dtype=jnp.int32)

    q = (xn @ params["wq"].astype(x.dtype)).reshape(b, 1, h,
                                                    m.d_nope + m.d_rope)
    q_nope, q_rope = q[..., :m.d_nope], q[..., m.d_nope:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)

    c_new = rmsnorm(xn @ params["w_dkv"].astype(x.dtype), params["kv_norm"],
                    cfg.norm_eps)
    kr_new = apply_rope((xn @ params["w_kr"].astype(x.dtype))[:, :, None, :],
                        positions, cfg.rope_theta)[:, :, 0, :]
    ckv = jax.lax.dynamic_update_slice_in_dim(cache["ckv"], c_new, pos,
                                              axis=1)
    kr = jax.lax.dynamic_update_slice_in_dim(cache["kr"], kr_new, pos, axis=1)

    # absorb W_uk into q: q' = q_nope . W_uk^T  -> (B, H, kv_lora)
    w_uk = params["w_uk"].reshape(m.kv_lora, h, m.d_nope)
    q_lat = jnp.einsum("bhd,lhd->bhl", q_nope[:, 0].astype(F32),
                       w_uk.astype(F32))
    s_len = ckv.shape[1]
    scores = (jnp.einsum("bhl,bsl->bhs", q_lat, ckv.astype(F32))
              + jnp.einsum("bhr,bsr->bhs", q_rope[:, 0].astype(F32),
                           kr.astype(F32)))
    scores = scores * ((m.d_nope + m.d_rope) ** -0.5)
    mask = jnp.arange(s_len)[None, None, :] < (pos + 1)
    scores = jnp.where(mask, scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    o_lat = jnp.einsum("bhs,bsl->bhl", p, ckv.astype(F32))  # (B, H, kv_lora)
    w_uv = params["w_uv"].astype(x.dtype).reshape(m.kv_lora, h, m.d_v)
    o = jnp.einsum("bhl,lhv->bhv", o_lat,
                   w_uv.astype(F32))                    # (B, H, d_v)
    o = o.reshape(b, 1, h * m.d_v).astype(x.dtype)
    new_cache = {"ckv": ckv, "kr": kr, "pos": pos + 1}
    return o @ params["wo"].astype(x.dtype), new_cache


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------
def mlp_meta(cfg: ModelConfig, d_ff: int | None = None) -> dict:
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    return {
        "wg": ParamMeta((d, f), ("embed", "ffn")),
        "wu": ParamMeta((d, f), ("embed", "ffn")),
        "wd": ParamMeta((f, d), ("ffn", "embed")),
        "norm": rmsnorm_meta(d),
    }


def mlp_apply(params, x, cfg: ModelConfig):
    xn = rmsnorm(x, params["norm"], cfg.norm_eps)
    h = jax.nn.silu(xn @ params["wg"].astype(x.dtype)) \
        * (xn @ params["wu"].astype(x.dtype))
    return h @ params["wd"].astype(x.dtype)


# ---------------------------------------------------------------------------
# MoE (top-k routing, capacity-based dispatch, optional shared experts)
# ---------------------------------------------------------------------------
def moe_meta(cfg: ModelConfig) -> dict:
    e = cfg.moe
    d = cfg.d_model
    meta = {
        "router": ParamMeta((d, e.n_experts), ("embed", None), scale=0.02),
        "wg": ParamMeta((e.n_experts, d, e.d_ff_expert),
                        ("experts", "embed", "ffn")),
        "wu": ParamMeta((e.n_experts, d, e.d_ff_expert),
                        ("experts", "embed", "ffn")),
        "wd": ParamMeta((e.n_experts, e.d_ff_expert, d),
                        ("experts", "ffn", "embed")),
        "norm": rmsnorm_meta(d),
    }
    if e.n_shared:
        meta["shared"] = {
            "wg": ParamMeta((d, e.d_ff_expert * e.n_shared), ("embed", "ffn")),
            "wu": ParamMeta((d, e.d_ff_expert * e.n_shared), ("embed", "ffn")),
            "wd": ParamMeta((e.d_ff_expert * e.n_shared, d), ("ffn", "embed")),
        }
    return meta


def moe_apply(params, x, cfg: ModelConfig, expert_sharding=None):
    """x: (B, S, d). Deterministic argsort dispatch with capacity drop.
    ``expert_sharding``: NamedSharding hint for the (E, capacity, d)
    dispatch buffers (expert-parallel over the model axis)."""
    def _eshard(t):
        if expert_sharding is not None:
            return jax.lax.with_sharding_constraint(t, expert_sharding)
        return t

    e = cfg.moe
    b, s, d = x.shape
    t = b * s
    xn = rmsnorm(x, params["norm"], cfg.norm_eps)
    xf = xn.reshape(t, d)

    logits = (xf @ params["router"].astype(x.dtype)).astype(F32)  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, expert = jax.lax.top_k(probs, e.top_k)                  # (T, k)
    gate = gate / jnp.maximum(jnp.sum(gate, axis=-1, keepdims=True), 1e-9)

    flat_e = expert.reshape(-1)                                    # (T*k,)
    flat_t = jnp.repeat(jnp.arange(t, dtype=jnp.int32), e.top_k)
    flat_g = gate.reshape(-1)

    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    starts = jnp.searchsorted(sorted_e, jnp.arange(e.n_experts),
                              side="left")
    pos_in_e = jnp.arange(t * e.top_k, dtype=jnp.int32) - starts[sorted_e]
    cap = max(8, int(t * e.top_k / e.n_experts * e.capacity_factor))
    if cap >= 128:  # shardable capacity (see expert_sharding)
        cap = -(-cap // 128) * 128
    keep = pos_in_e < cap
    slot = jnp.where(keep, sorted_e * cap + pos_in_e, t * e.top_k)  # drop ->
    tok = flat_t[order]

    xbuf = jnp.zeros((e.n_experts * cap + 1, d), x.dtype)
    xbuf = xbuf.at[slot].set(xf[tok])
    xe = _eshard(xbuf[:-1].reshape(e.n_experts, cap, d))

    h = _eshard(jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe,
                                       params["wg"].astype(x.dtype)))
                * jnp.einsum("ecd,edf->ecf", xe,
                             params["wu"].astype(x.dtype)))
    ye = _eshard(jnp.einsum("ecf,efd->ecd", h, params["wd"].astype(x.dtype)))
    ybuf = ye.reshape(e.n_experts * cap, d)

    contrib = jnp.where(keep, flat_g[order], 0.0)[:, None].astype(x.dtype) \
        * ybuf[jnp.minimum(slot, e.n_experts * cap - 1)]
    y = jnp.zeros((t, d), x.dtype).at[tok].add(contrib)

    if "shared" in params:
        sh = params["shared"]
        hs = jax.nn.silu(xf @ sh["wg"].astype(x.dtype)) \
            * (xf @ sh["wu"].astype(x.dtype))
        y = y + hs @ sh["wd"].astype(x.dtype)
    return y.reshape(b, s, d)


def moe_apply_shardmap(params, x, cfg: ModelConfig, mesh, dp_axes=None,
                       ep_axis: str = "model", fsdp: bool = False):
    """Expert-parallel MoE via shard_map: per-device LOCAL routing.

    Layout facts this exploits: activations x are sharded over the data
    axes and *replicated* across the model axis; expert weights are sharded
    over the model axis. So every device already holds (its token slice,
    its expert slice): route the local tokens locally, compute the local
    experts, combine partial outputs with one psum over the model axis —
    the same single collective a TP MLP needs. No global argsort, no
    cross-shard scatter (GSPMD's auto-partitioned global dispatch replicates
    those "as a last resort"). Capacity is enforced per (data shard,
    expert) — standard practice. Under FSDP the expert weights arrive
    data-sharded and are all-gathered per layer (the FSDP contract).
    """
    e = cfg.moe
    b, s, d = x.shape

    wspec = P(ep_axis, "data" if fsdp else None, None)
    wdspec = P(ep_axis, None, "data" if fsdp else None)
    especs = {"router": P(), "norm": P(), "wg": wspec, "wu": wspec,
              "wd": wdspec}
    if "shared" in params:
        especs["shared"] = {
            "wg": P("data" if fsdp else None, ep_axis),
            "wu": P("data" if fsdp else None, ep_axis),
            "wd": P(ep_axis, "data" if fsdp else None)}
    xspec = P(dp_axes, None, None)

    def gather(w, ax):
        return (jax.lax.all_gather(w, "data", axis=ax, tiled=True)
                if fsdp else w)

    def local(p, xl):
        bl, sl, _ = xl.shape
        t = bl * sl
        xn = rmsnorm(xl, p["norm"], cfg.norm_eps)
        xf = xn.reshape(t, d)
        logits = (xf @ p["router"].astype(xl.dtype)).astype(F32)
        probs = jax.nn.softmax(logits, axis=-1)
        gate, expert = jax.lax.top_k(probs, e.top_k)
        gate = gate / jnp.maximum(jnp.sum(gate, axis=-1, keepdims=True),
                                  1e-9)
        flat_e = expert.reshape(-1)
        flat_t = jnp.repeat(jnp.arange(t, dtype=jnp.int32), e.top_k)
        flat_g = gate.reshape(-1)
        order = jnp.argsort(flat_e, stable=True)
        sorted_e = flat_e[order]
        starts = jnp.searchsorted(sorted_e, jnp.arange(e.n_experts),
                                  side="left")
        pos = jnp.arange(t * e.top_k, dtype=jnp.int32) - starts[sorted_e]
        cap = max(8, int(t * e.top_k / e.n_experts * e.capacity_factor))
        keep = pos < cap
        # keep only this device's experts
        wg = gather(p["wg"], 1)
        wu = gather(p["wu"], 1)
        wd = gather(p["wd"], 2)
        e_loc = wg.shape[0]
        e_lo = jax.lax.axis_index(ep_axis) * e_loc
        mine = (sorted_e >= e_lo) & (sorted_e < e_lo + e_loc) & keep
        slot = jnp.where(mine, (sorted_e - e_lo) * cap + pos, e_loc * cap)
        tok = flat_t[order]
        xbuf = jnp.zeros((e_loc * cap + 1, d), xl.dtype)
        xbuf = xbuf.at[slot].set(jnp.where(mine[:, None], xf[tok], 0))
        xe = xbuf[:-1].reshape(e_loc, cap, d)
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe,
                                   wg.astype(xl.dtype))) \
            * jnp.einsum("ecd,edf->ecf", xe, wu.astype(xl.dtype))
        ye = jnp.einsum("ecf,efd->ecd", h, wd.astype(xl.dtype))
        ybuf = ye.reshape(e_loc * cap, d)
        contrib = jnp.where(mine, flat_g[order], 0.0)[:, None].astype(
            xl.dtype) * ybuf[jnp.minimum(slot, e_loc * cap - 1)]
        y = jnp.zeros((t, d), xl.dtype).at[tok].add(contrib)
        if "shared" in p:
            sh = p["shared"]
            swg = gather(sh["wg"], 0)
            swu = gather(sh["wu"], 0)
            swd = gather(sh["wd"], 1)
            hs = jax.nn.silu(xf @ swg.astype(xl.dtype)) \
                * (xf @ swu.astype(xl.dtype))
            y = y + hs @ swd.astype(xl.dtype)
        y = jax.lax.psum(y, ep_axis)
        return y.reshape(bl, sl, d)

    sm = jax.shard_map(local, mesh=mesh, in_specs=(especs, xspec),
                       out_specs=xspec, check_vma=False)
    return sm(params, x)


def moe_aux_loss(params, x, cfg: ModelConfig):
    """Load-balancing auxiliary loss (Switch-style)."""
    e = cfg.moe
    b, s, d = x.shape
    xn = rmsnorm(x, params["norm"], cfg.norm_eps)
    logits = (xn.reshape(-1, d) @ params["router"].astype(x.dtype)).astype(F32)
    probs = jax.nn.softmax(logits, axis=-1)
    _, expert = jax.lax.top_k(probs, e.top_k)
    counts = jnp.zeros(e.n_experts, F32).at[expert.reshape(-1)].add(1.0)
    frac_tokens = counts / jnp.maximum(jnp.sum(counts), 1.0)
    frac_probs = jnp.mean(probs, axis=0)
    return e.n_experts * jnp.sum(frac_tokens * frac_probs)


# ---------------------------------------------------------------------------
# Mamba2 (SSD — state space duality, chunked scan)
# ---------------------------------------------------------------------------
def mamba_meta(cfg: ModelConfig) -> dict:
    s = cfg.ssm
    d = cfg.d_model
    di = s.d_inner(d)
    nh = s.n_heads(d)
    conv_dim = di + 2 * s.n_groups * s.d_state
    return {
        "in_proj": ParamMeta(
            (d, 2 * di + 2 * s.n_groups * s.d_state + nh),
            ("embed", "heads_dh")),
        "conv_w": ParamMeta((s.conv_width, conv_dim), (None, "heads_dh"),
                            scale=0.5),
        "conv_b": ParamMeta((conv_dim,), ("heads_dh",), init="zeros"),
        "a_log": ParamMeta((nh,), ("heads",), init="zeros"),
        "d_skip": ParamMeta((nh,), ("heads",), init="ones"),
        "dt_bias": ParamMeta((nh,), ("heads",), init="zeros"),
        "out_norm": ParamMeta((di,), ("heads_dh",), init="ones"),
        "out_proj": ParamMeta((di, d), ("heads_dh", "embed")),
        "norm": rmsnorm_meta(d),
    }


def _mamba_split(params, xn, cfg: ModelConfig):
    s = cfg.ssm
    d = cfg.d_model
    di = s.d_inner(d)
    gn = s.n_groups * s.d_state
    nh = s.n_heads(d)
    proj = xn @ params["in_proj"].astype(xn.dtype)
    z, xbc_dt = jnp.split(proj, [di], axis=-1)
    xbc, dt = jnp.split(xbc_dt, [di + 2 * gn], axis=-1)
    return z, xbc, dt, di, gn, nh


def _causal_conv(xbc, w, b, prev=None):
    """Depthwise causal conv along seq. xbc: (B, S, C); w: (W, C)."""
    width = w.shape[0]
    if prev is None:
        prev = jnp.zeros((xbc.shape[0], width - 1, xbc.shape[2]), xbc.dtype)
    xp = jnp.concatenate([prev, xbc], axis=1)
    out = jnp.zeros_like(xbc)
    for i in range(width):
        out = out + xp[:, i:i + xbc.shape[1]] * w[i].astype(xbc.dtype)
    return jax.nn.silu(out + b.astype(xbc.dtype)), xp[:, -(width - 1):]


def mamba_apply(params, x, cfg: ModelConfig):
    """Chunked SSD forward (training/prefill). Returns (out, final_state)."""
    s = cfg.ssm
    b, S0, d = x.shape
    xn = rmsnorm(x, params["norm"], cfg.norm_eps)
    # pad at the FRONT to a whole number of chunks: with zero inputs and a
    # zero initial state this is exact (zero tokens add nothing; decay of a
    # zero state is zero), unlike tail padding which would corrupt the
    # carried-out state.
    front = (-S0) % min(s.chunk, max(S0, 1))
    if front:
        xn = jnp.pad(xn, ((0, 0), (front, 0), (0, 0)))
    S = S0 + front
    z, xbc, dt, di, gn, nh = _mamba_split(params, xn, cfg)
    xbc, conv_tail = _causal_conv(xbc, params["conv_w"], params["conv_b"])
    xs, B_, C_ = jnp.split(xbc, [di, di + gn], axis=-1)
    p = s.head_dim
    n = s.d_state
    g = s.n_groups
    xs = xs.reshape(b, S, nh, p)
    # Keep B/C in their (G << heads) group form: broadcasting them to all
    # heads would materialize (B,S,heads,N) tensors (0.5 GiB+ at scale) and
    # make the inter-position dot products redundantly per-head.
    B_ = B_.reshape(b, S, g, n)
    C_ = C_.reshape(b, S, g, n)
    dt = jax.nn.softplus(dt.astype(F32)
                         + params["dt_bias"].astype(F32))   # (B,S,nh)
    a = -jnp.exp(params["a_log"].astype(F32))               # (nh,)
    da = dt * a                                             # (B,S,nh)

    cl = min(s.chunk, S)
    assert S % cl == 0
    nc = S // cl
    hg = nh // g                                            # heads per group

    # checkpointed: the chunk scan's backward otherwise saves every chunk's
    # (cl x cl x heads) decay/score matrices across all chunks & layers
    @jax.checkpoint
    def chunk_fn(state, inp):
        # xc (B,cl,nh,P); bc/cc (B,cl,G,N); dac/dtc (B,cl,nh)
        xc, bc, cc, dac, dtc = inp
        cum = jnp.cumsum(dac, axis=1)                       # (B,cl,nh)
        seg = cum[:, :, None, :] - cum[:, None, :, :]       # (B,i,j,nh)
        il = jnp.arange(cl)
        causal = il[:, None] >= il[None, :]
        L = jnp.where(causal[None, :, :, None], jnp.exp(seg), 0.0)
        sc = jnp.einsum("bign,bjgn->bijg", cc.astype(F32),
                        bc.astype(F32))                     # (B,i,j,G)
        sch = jnp.repeat(sc, hg, axis=3) if g > 1 else sc   # broadcast ok
        w = sch * L * dtc[:, None, :, :]                    # (B,i,j,nh)
        y_intra = jnp.einsum("bijh,bjhp->bihp", w, xc.astype(F32))
        # contribution of carried-in state (state: (B,nh,N,P))
        if g == 1:
            y_inter = jnp.einsum(
                "bin,bhnp->bihp", cc[:, :, 0].astype(F32), state) \
                * jnp.exp(cum)[..., None]
        else:
            cexp = jnp.repeat(cc, hg, axis=2).astype(F32) \
                * jnp.exp(cum)[..., None]
            y_inter = jnp.einsum("bihn,bhnp->bihp", cexp, state)
        # new state
        decay_to_end = jnp.exp(cum[:, -1:, :] - cum)        # (B,cl,nh)
        if g == 1:
            sstate = jnp.einsum("bjn,bjh,bjhp->bhnp",
                                bc[:, :, 0].astype(F32),
                                (dtc * decay_to_end),
                                xc.astype(F32))
        else:
            bch = jnp.repeat(bc, hg, axis=2).astype(F32)
            sstate = jnp.einsum("bjhn,bjh,bjhp->bhnp", bch,
                                (dtc * decay_to_end), xc.astype(F32))
        state = state * jnp.exp(cum[:, -1])[..., None, None] + sstate
        return state, (y_intra + y_inter)

    xs_c = xs.reshape(b, nc, cl, nh, p).swapaxes(0, 1)
    B_c = B_.reshape(b, nc, cl, g, n).swapaxes(0, 1)
    C_c = C_.reshape(b, nc, cl, g, n).swapaxes(0, 1)
    da_c = da.reshape(b, nc, cl, nh).swapaxes(0, 1)
    dt_c = dt.reshape(b, nc, cl, nh).swapaxes(0, 1)
    state0 = jnp.zeros((b, nh, n, p), F32)
    final_state, ys = jax.lax.scan(chunk_fn, state0,
                                   (xs_c, B_c, C_c, da_c, dt_c))
    y = ys.swapaxes(0, 1).reshape(b, S, nh, p)
    y = y + xs.astype(F32) * params["d_skip"].astype(F32)[None, None, :, None]
    y = y.reshape(b, S, di).astype(x.dtype)
    y = rmsnorm(y * jax.nn.silu(z), params["out_norm"], cfg.norm_eps)
    out = (y @ params["out_proj"].astype(x.dtype))[:, front:]
    return out, {"state": final_state, "conv": conv_tail}


def mamba_decode(params, x, cache, cfg: ModelConfig):
    """Single-token recurrent step. cache: {"state": (B,nh,N,P),
    "conv": (B,W-1,conv_dim)}."""
    s = cfg.ssm
    b = x.shape[0]
    xn = rmsnorm(x, params["norm"], cfg.norm_eps)
    z, xbc, dt, di, gn, nh = _mamba_split(params, xn, cfg)
    xbc, conv_tail = _causal_conv(xbc, params["conv_w"], params["conv_b"],
                                  prev=cache["conv"])
    xs, B_, C_ = jnp.split(xbc, [di, di + gn], axis=-1)
    p, n, g = s.head_dim, s.d_state, s.n_groups
    rep = nh // g
    xs = xs.reshape(b, nh, p)
    Bh = jnp.repeat(B_.reshape(b, g, n), rep, axis=1)
    Ch = jnp.repeat(C_.reshape(b, g, n), rep, axis=1)
    dt1 = jax.nn.softplus(dt.astype(F32)[:, 0]
                          + params["dt_bias"].astype(F32))   # (B,nh)
    a = -jnp.exp(params["a_log"].astype(F32))
    decay = jnp.exp(dt1 * a)                                 # (B,nh)
    state = cache["state"] * decay[..., None, None] \
        + jnp.einsum("bhn,bh,bhp->bhnp", Bh.astype(F32), dt1,
                     xs.astype(F32))
    y = jnp.einsum("bhn,bhnp->bhp", Ch.astype(F32), state)
    y = y + xs.astype(F32) * params["d_skip"].astype(F32)[None, :, None]
    y = y.reshape(b, 1, di).astype(x.dtype)
    y = rmsnorm(y * jax.nn.silu(z), params["out_norm"], cfg.norm_eps)
    out = y @ params["out_proj"].astype(x.dtype)
    return out, {"state": state, "conv": conv_tail}
