"""Pallas TPU kernel: Base-Delta-Immediate compressibility detection.

Computes, per 64-byte line, the best BDI scheme and its encoded size — the
hot inner loop when scanning large tensors/traces for compressibility
(Section 10's BDI encoding; full byte packing happens offline in
``repro.core.encodings``, which this kernel must agree with bit-exactly).

Scheme ids: 0=raw(64 B) 1=zeros(1) 2=rep8(8) 3=b8d1(16) 4=b8d2(24)
5=b8d4(40) 6=rep4(4) 7=b4d1(20) 8=b4d2(36) 9=rep2(2) 10=b2d1(34)

Input  bytes (N, 64) int32 (values 0..255)
Output sizes (N,) int32, schemes (N,) int32

Arithmetic notes (TPU lanes are 32-bit):
* 2-byte bases: sign-extended into int32, exact signed deltas.
* 4-byte bases: int32 subtraction with explicit signed-overflow detection
  (overflowing deltas cannot fit any 1/2-byte range).
* 8-byte bases: two uint32 limbs with borrow; matches the oracle's int64
  mod-2^64 semantics limb-for-limb.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import cdiv, interpret_default, pad_to

BLOCK_N = 512

SCHEME_SIZES = {0: 64, 1: 1, 2: 8, 3: 16, 4: 24, 5: 40, 6: 4, 7: 20,
                8: 36, 9: 2, 10: 34}


def _take(cond, size, scheme, bs, bsch):
    upd = cond & (size < bs)
    return jnp.where(upd, size, bs), jnp.where(upd, scheme, bsch)


def _kernel(b_ref, size_ref, scheme_ref):
    by = b_ref[...]                                   # (BN, 64) int32
    n = by.shape[0]
    best_size = jnp.full((n,), 64, dtype=jnp.int32)
    best_scheme = jnp.zeros((n,), dtype=jnp.int32)

    zeros = jnp.all(by == 0, axis=1)
    best_size, best_scheme = _take(zeros, 1, 1, best_size, best_scheme)

    # ---- 8-byte bases: two uint32 limbs ---------------------------------
    byu = by.astype(jnp.uint32)
    lo8 = (byu[:, 0::8] | (byu[:, 1::8] << 8) | (byu[:, 2::8] << 16)
           | (byu[:, 3::8] << 24))                    # (BN, 8)
    hi8 = (byu[:, 4::8] | (byu[:, 5::8] << 8) | (byu[:, 6::8] << 16)
           | (byu[:, 7::8] << 24))
    d_lo = lo8 - lo8[:, :1]
    borrow = (lo8 < lo8[:, :1]).astype(jnp.uint32)
    d_hi = hi8 - hi8[:, :1] - borrow
    rep8 = jnp.all((d_lo == 0) & (d_hi == 0), axis=1)
    best_size, best_scheme = _take(rep8, 8, 2, best_size, best_scheme)
    ffff = jnp.uint32(0xFFFFFFFF)
    for db, scheme in ((1, 3), (2, 4), (4, 5)):
        if db < 4:
            half = jnp.uint32(1 << (8 * db - 1))
            pos = (d_hi == 0) & (d_lo < half)
            neg = (d_hi == ffff) & (d_lo >= (jnp.uint32(0) - half))
        else:
            pos = (d_hi == 0) & (d_lo < jnp.uint32(0x80000000))
            neg = (d_hi == ffff) & (d_lo >= jnp.uint32(0x80000000))
        fits = jnp.all(pos | neg, axis=1)
        best_size, best_scheme = _take(fits & ~rep8, 8 + 8 * db, scheme,
                                       best_size, best_scheme)

    # ---- 4-byte bases: int32 with overflow detection ---------------------
    v4 = (lo8.reshape(n, 8, 1), hi8.reshape(n, 8, 1))
    v4 = jnp.concatenate(v4, axis=2).reshape(n, 16).astype(jnp.int32)
    b4 = v4[:, :1]
    d4 = v4 - b4                                      # wraps on overflow
    ovf = ((v4 < 0) != (b4 < 0)) & ((d4 < 0) == (b4 < 0))
    rep4 = jnp.all((d4 == 0) & ~ovf, axis=1)
    best_size, best_scheme = _take(rep4, 4, 6, best_size, best_scheme)
    for db, scheme in ((1, 7), (2, 8)):
        half = 1 << (8 * db - 1)
        fits = jnp.all(~ovf & (d4 >= -half) & (d4 < half), axis=1)
        best_size, best_scheme = _take(fits & ~rep4, 4 + 16 * db, scheme,
                                       best_size, best_scheme)

    # ---- 2-byte bases: exact in int32 -------------------------------------
    v2 = (by[:, 0::2] | (by[:, 1::2] << 8)).astype(jnp.int32)  # (BN, 32)
    v2 = ((v2 ^ 0x8000) - 0x8000)                     # sign-extend 16 -> 32
    d2 = v2 - v2[:, :1]
    rep2 = jnp.all(d2 == 0, axis=1)
    best_size, best_scheme = _take(rep2, 2, 9, best_size, best_scheme)
    fits2 = jnp.all((d2 >= -128) & (d2 < 128), axis=1)
    best_size, best_scheme = _take(fits2 & ~rep2, 2 + 32, 10,
                                   best_size, best_scheme)

    size_ref[...] = best_size
    scheme_ref[...] = best_scheme


def bdi_sizes_pallas(bytes_i32: jax.Array, block_n: int = BLOCK_N,
                     interpret: bool | None = None):
    """(N, 64) int32 bytes -> (sizes (N,), schemes (N,)) int32."""
    if interpret is None:
        interpret = interpret_default()
    x, n = pad_to(bytes_i32.astype(jnp.int32), block_n, axis=0)
    grid = (cdiv(x.shape[0], block_n),)
    sizes, schemes = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block_n, 64), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((block_n,), lambda i: (i,)),
                   pl.BlockSpec((block_n,), lambda i: (i,))],
        out_shape=[jax.ShapeDtypeStruct((x.shape[0],), jnp.int32),
                   jax.ShapeDtypeStruct((x.shape[0],), jnp.int32)],
        interpret=interpret,
        name="bdi",
    )(x)
    return sizes[:n], schemes[:n]
