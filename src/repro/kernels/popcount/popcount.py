"""Pallas TPU kernel: per-cache-line population count.

Input  (N, 16) uint32  — 64-byte lines as 16 words
Output (N,)    int32   — number of set bits per line

Tiling: blocks of (BLOCK_N, 16) words live in VMEM; the popcount is pure
VPU bit arithmetic (shifts/ands/multiplies), no MXU use. BLOCK_N = 1024
keeps the block at 64 KiB — far under VMEM while amortizing grid overhead.
The 16-wide lane dimension under-fills the 128-lane VREG; the fused
vampire_energy kernel avoids this by keeping the reduction in-kernel, and
`ops.line_ones_flat` offers a (N*16 -> 128-lane) layout variant for pure
throughput use.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.dram import popcount_u32 as _popcount_u32
from repro.kernels.common import cdiv, interpret_default, pad_to

BLOCK_N = 1024


def _kernel(x_ref, o_ref):
    x = x_ref[...]                       # (BLOCK_N, 16) uint32
    o_ref[...] = jnp.sum(_popcount_u32(x), axis=1)


def line_ones_pallas(lines: jax.Array, block_n: int = BLOCK_N,
                     interpret: bool | None = None) -> jax.Array:
    """(N, 16) uint32 -> (N,) int32 ones per line."""
    if interpret is None:
        interpret = interpret_default()
    x, n = pad_to(lines.astype(jnp.uint32), block_n, axis=0)
    grid = (cdiv(x.shape[0], block_n),)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block_n, 16), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_n,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((x.shape[0],), jnp.int32),
        interpret=interpret,
        name="popcount",
    )(x)
    return out[:n]
