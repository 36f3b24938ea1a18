"""Pallas TPU kernel: bus-toggle count between consecutive cache lines.

Inputs  cur  (N, 16) uint32 — line on the bus at step i
        prev (N, 16) uint32 — line on the bus at step i-1 (precomputed shift)
Output  (N,) int32          — wires toggling = popcount(cur ^ prev)

Same VMEM tiling as the popcount kernel; the XOR is fused with the
popcount so the (N,16) intermediate never round-trips to HBM.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import cdiv, interpret_default, pad_to
from repro.kernels.popcount.popcount import _popcount_u32

BLOCK_N = 1024


def _kernel(cur_ref, prev_ref, o_ref):
    x = jnp.bitwise_xor(cur_ref[...], prev_ref[...])
    o_ref[...] = jnp.sum(_popcount_u32(x), axis=1)


def line_toggles_pallas(cur: jax.Array, prev: jax.Array,
                        block_n: int = BLOCK_N,
                        interpret: bool | None = None) -> jax.Array:
    if interpret is None:
        interpret = interpret_default()
    cur, n = pad_to(cur.astype(jnp.uint32), block_n, axis=0)
    prev, _ = pad_to(prev.astype(jnp.uint32), block_n, axis=0)
    grid = (cdiv(cur.shape[0], block_n),)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block_n, 16), lambda i: (i, 0)),
                  pl.BlockSpec((block_n, 16), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_n,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((cur.shape[0],), jnp.int32),
        interpret=interpret,
        name="toggle",
    )(cur, prev)
    return out[:n]
