"""Pallas TPU kernels for per-chunk int8 quantize / dequantize.

One grid step handles one PS chunk (chunk_elems elements viewed as
(chunk_elems/128, 128)); the chunk's amax reduction, scale computation and
rounding all happen in a single VMEM pass.  Scales are emitted as one f32 per
chunk (the per-chunk metadata the paper's PS keeps besides the payload).

The scale operand is viewed as (C, 1, 1) with (1, 1, 1) blocks: the TPU
compiler takes a block whose last two dimensions equal the array's, where a
(1, 1) block of a (C, 1) array is refused as unaligned to the (8, 128) tile.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128


def _quant_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(x / scale), -127, 127)
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = jnp.full(s_ref.shape, scale, jnp.float32)


def _dequant_kernel(q_ref, s_ref, x_ref):
    x_ref[...] = q_ref[...].astype(jnp.float32) * s_ref[0]


def quantize_chunks_pallas(
    x: jax.Array, chunk_elems: int, *, interpret: bool
) -> tuple[jax.Array, jax.Array]:
    """Pallas per-chunk symmetric int8 quantize of an (N,) f32 slab.

    Grid step ``i`` owns chunk ``i``: computes ``scale = amax/127`` (1.0
    for an all-zero chunk) and ``q = clip(round(x/scale), ±127)``.  Returns
    ((N,) int8 payload, (N/chunk_elems,) f32 scales)."""
    n = x.shape[0]
    if n % chunk_elems or chunk_elems % LANES:
        raise ValueError(f"bad sizes n={n} chunk={chunk_elems}")
    c = n // chunk_elems
    rows = chunk_elems // LANES
    x2 = x.reshape(c * rows, LANES)
    q2, s2 = pl.pallas_call(
        _quant_kernel,
        grid=(c,),
        in_specs=[pl.BlockSpec((rows, LANES), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((1, 1, 1), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((c * rows, LANES), jnp.int8),
            jax.ShapeDtypeStruct((c, 1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x2)
    return q2.reshape(n), s2.reshape(c)


def dequantize_chunks_pallas(
    q: jax.Array, scale: jax.Array, chunk_elems: int, *, interpret: bool
) -> jax.Array:
    """Pallas per-chunk int8 dequantize: ``f32(q) * scale[chunk]``.

    Inverse of :func:`quantize_chunks_pallas`; the same expression runs
    in-register inside the fused wire-path kernel, which is what makes the
    fused and unfused decode bit-identical."""
    n = q.shape[0]
    c = n // chunk_elems
    rows = chunk_elems // LANES
    q2 = q.reshape(c * rows, LANES)
    s2 = scale.reshape(c, 1, 1)
    x2 = pl.pallas_call(
        _dequant_kernel,
        grid=(c,),
        in_specs=[
            pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((1, 1, 1), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((c * rows, LANES), jnp.float32),
        interpret=interpret,
    )(q2, s2)
    return x2.reshape(n)
