"""jit'd surface of the causal flash-attention kernel.

Validation lives here, before the jit'd body, so errors speak in the
caller's shapes: q (B, S, H, D) and k/v (B, S, Hkv, D) with equal B, S and
D, H a multiple of Hkv, D a multiple of 128 lanes, and S a whole number of
``block`` tiles.  :func:`kernel_block` is the tile the shapes give and
:func:`kernel_fits` the rule a caller uses to choose this kernel over a
plain jnp path.
"""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels import interpret_mode
from repro.kernels.attention.kernel import LANES, causal_attention_pallas

MAX_BLOCK = 512


def kernel_block(seq: int) -> int:
    """The query and key/value tile for a sequence of ``seq`` tokens."""
    return min(MAX_BLOCK, seq)


def kernel_fits(seq: int, head_dim: int) -> bool:
    """Whether the kernel takes these shapes: ``seq`` a whole number of
    :func:`kernel_block` tiles, each a multiple of 128 lanes, and
    ``head_dim`` a multiple of 128."""
    block = kernel_block(seq)
    return seq % block == 0 and block % LANES == 0 and head_dim % LANES == 0


def causal_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                     block: int | None = None,
                     interpret: bool | None = None) -> jax.Array:
    """Causal self-attention, q (B, S, H, D) over k/v (B, S, Hkv, D).

    Query head ``h`` reads key/value head ``h // (H // Hkv)``; position
    ``i`` attends to ``0..i``; the scale is ``1/sqrt(D)``.  Differentiable
    in q, k and v.  ``block`` defaults to :func:`kernel_block` of S."""
    if q.ndim != 4 or k.shape != v.shape or k.ndim != 4:
        raise ValueError(f"expected q (B,S,H,D) and k, v (B,S,Hkv,D) alike, "
                         f"got {q.shape}, {k.shape}, {v.shape}")
    (b, s, h, d), (bk, sk, hkv, dk) = q.shape, k.shape
    if (b, s, d) != (bk, sk, dk) or h % hkv:
        raise ValueError(f"q {q.shape} and k/v {k.shape} disagree on batch, "
                         f"sequence or head size, or {h} heads are not a "
                         f"multiple of {hkv} key/value heads")
    if not q.dtype == k.dtype == v.dtype:
        raise ValueError(f"q, k, v dtypes differ: {q.dtype}, {k.dtype}, {v.dtype}")
    block = kernel_block(s) if block is None else block
    if s % block or block % LANES or d % LANES:
        raise ValueError(f"sequence {s} must be a whole number of {block}-token "
                         f"blocks, and the block ({block}) and head size "
                         f"({d}) multiples of {LANES}")
    return _causal_attention(q, k, v, block, interpret_mode(interpret))


@partial(jax.jit, static_argnames=("block", "interpret"))
def _causal_attention(q, k, v, block, interpret):
    return causal_attention_pallas(q, k, v, block=block, interpret=interpret)
