"""jit'd wrappers for the chunk quantization codec.

Argument validation lives here, at the public boundary (the Pallas/ref
implementations assume clean shapes): slabs must be flat f32 and a whole
number of ``chunk_elems`` chunks, payloads must be int8 with one f32
scale per chunk.  Raising before the jit'd body keeps the error messages
at the caller's shapes instead of a reshape failure deep in the kernel.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.quant.kernel import (
    LANES,
    dequantize_chunks_pallas,
    quantize_chunks_pallas,
)
from repro.kernels.quant.ref import dequantize_chunks_ref, quantize_chunks_ref


def _check_chunking(n: int, chunk_elems: int) -> None:
    if chunk_elems < LANES or chunk_elems % LANES:
        raise ValueError(
            f"chunk_elems {chunk_elems} must be a positive multiple of "
            f"{LANES} lanes")
    if n == 0 or n % chunk_elems:
        raise ValueError(
            f"slab of {n} elements is not a whole number of "
            f"{chunk_elems}-element chunks")


@partial(jax.jit, static_argnames=("chunk_elems", "use_pallas", "interpret"))
def quantize_chunks(x, chunk_elems: int, *, use_pallas: bool = True,
                    interpret: bool | None = None):
    """Quantize a flat f32 slab to (int8 payload, per-chunk f32 scales)."""
    if x.ndim != 1:
        raise ValueError(f"expected a flat slab, got shape {x.shape}")
    if x.dtype != jnp.float32:
        raise ValueError(f"quantize_chunks wants f32 input, got {x.dtype}")
    _check_chunking(x.shape[0], chunk_elems)
    if not use_pallas:
        return quantize_chunks_ref(x, chunk_elems)
    return quantize_chunks_pallas(x, chunk_elems,
                                  interpret=interpret_mode(interpret))


@partial(jax.jit, static_argnames=("chunk_elems", "use_pallas", "interpret"))
def dequantize_chunks(q, scale, chunk_elems: int, *, use_pallas: bool = True,
                      interpret: bool | None = None):
    """Decode an (int8 payload, per-chunk f32 scales) pair back to f32."""
    if q.ndim != 1:
        raise ValueError(f"expected a flat payload, got shape {q.shape}")
    if q.dtype != jnp.int8:
        raise ValueError(f"dequantize_chunks wants an int8 payload, got {q.dtype}")
    _check_chunking(q.shape[0], chunk_elems)
    c = q.shape[0] // chunk_elems
    if scale.shape != (c,):
        raise ValueError(
            f"payload of {c} chunks needs scales of shape ({c},), got "
            f"{scale.shape}")
    if not use_pallas:
        return dequantize_chunks_ref(q, scale, chunk_elems)
    return dequantize_chunks_pallas(q, scale, chunk_elems,
                                    interpret=interpret_mode(interpret))
