"""Pallas TPU kernel: blockwise (flash) attention with online softmax.

Used by the framework's long-context paths (prefill_32k / long_500k shapes),
where materializing (S, S) scores is impossible. Grid = (batch*q_heads,
q_blocks, kv_blocks); the TPU executes the last grid axis sequentially, so
the running max / normalizer / accumulator live in VMEM scratch across the
kv sweep and the output is finalized on the last kv block.

GQA is handled in the index maps: kv tensors are indexed by
``head // group_size``, so grouped K/V are never materialized per-q-head.

Shapes: q (BH, S_q, D), k/v (BH_kv, S_kv, D) -> out (BH, S_q, D).
Causal masking compares global q/k positions built from program ids.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import cdiv, interpret_default

DEFAULT_BLOCK_Q = 256
DEFAULT_BLOCK_K = 256
NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            sm_scale: float, causal: bool, block_q: int, block_k: int,
            kv_blocks: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)          # (BQ, D)
    k = k_ref[0].astype(jnp.float32)          # (BK, D)
    v = v_ref[0].astype(jnp.float32)          # (BK, D)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale
    if causal:
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 0)
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)

    m_prev = m_scr[...]                        # (BQ, 1)
    l_prev = l_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)                     # (BQ, BK)
    alpha = jnp.exp(m_prev - m_new)            # (BQ, 1)
    l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ki == kv_blocks - 1)
    def _finalize():
        o_ref[0] = (acc_scr[...]
                    / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "sm_scale", "block_q", "block_k", "interpret"))
def flash_attention_pallas(q, k, v, *, causal: bool = True,
                           sm_scale: float | None = None,
                           block_q: int = DEFAULT_BLOCK_Q,
                           block_k: int = DEFAULT_BLOCK_K,
                           interpret: bool | None = None):
    """q (BH, Sq, D); k, v (BH_kv, Skv, D) with BH % BH_kv == 0."""
    if interpret is None:
        interpret = interpret_default()
    bh, sq, d = q.shape
    bh_kv, skv, _ = k.shape
    assert bh % bh_kv == 0, (bh, bh_kv)
    group = bh // bh_kv
    if sm_scale is None:
        sm_scale = d ** -0.5
    block_q = min(block_q, sq)
    block_k = min(block_k, skv)
    nq, nk = cdiv(sq, block_q), cdiv(skv, block_k)
    assert sq % block_q == 0 and skv % block_k == 0, "pad seq to block size"

    from jax.experimental.pallas import tpu as pltpu
    kernel = functools.partial(
        _kernel, sm_scale=float(sm_scale), causal=causal,
        block_q=block_q, block_k=block_k, kv_blocks=nk)
    return pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda b, qi, ki, group=group: (b // group, ki, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda b, qi, ki, group=group: (b // group, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, 1), jnp.float32),
                        pltpu.VMEM((block_q, 1), jnp.float32),
                        pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)
