"""Pallas TPU kernel: 256-entry byte LUT via one-hot MXU matmul.

This is the TPU-native reformulation of the paper's in-DRAM encoding table
(Section 10.1): instead of a scalar SRAM lookup per byte (no efficient
per-lane gather on the TPU VPU), each block of bytes is one-hot expanded and
multiplied against the LUT as a (BLOCK_B, 256) x (256, 1) matmul on the MXU.

Input  bytes (M,) int32 in [0,256)   (M = 64 * n_lines)
       lut   (256,) int32
Output (M,) int32 encoded bytes

Tiling: BLOCK_B = 2048 bytes -> one-hot (2048, 256) f32 = 2 MiB in VMEM.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import cdiv, interpret_default, pad_to

BLOCK_B = 2048


def _kernel(b_ref, lut_ref, o_ref):
    b = b_ref[...]                                  # (BLOCK_B,) int32
    lut = lut_ref[...].astype(jnp.float32)          # (256,)
    onehot = (b[:, None] == jax.lax.broadcasted_iota(
        jnp.int32, (b.shape[0], 256), 1)).astype(jnp.float32)
    enc = jax.lax.dot_general(
        onehot, lut[:, None], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)         # (BLOCK_B, 1) on the MXU
    o_ref[...] = enc[:, 0].astype(jnp.int32)


def byte_lut_pallas(b: jax.Array, lut: jax.Array, block_b: int = BLOCK_B,
                    interpret: bool | None = None) -> jax.Array:
    if interpret is None:
        interpret = interpret_default()
    b32 = b.astype(jnp.int32)
    x, n = pad_to(b32, block_b, axis=0)
    grid = (cdiv(x.shape[0], block_b),)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block_b,), lambda i: (i,)),
                  pl.BlockSpec((256,), lambda i: (0,))],
        out_specs=pl.BlockSpec((block_b,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((x.shape[0],), jnp.int32),
        interpret=interpret,
        name="byte_lut",
    )(x, lut.astype(jnp.int32))
    return out[:n]
