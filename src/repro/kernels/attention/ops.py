"""jit'd surface of the causal flash-attention kernel.

Validation lives here, before the jit'd body, so errors speak in the
caller's shapes: q (B, S, H, Dqk), k (B, S, Hkv, Dqk) and v (B, S, Hkv, Dv)
with equal B and S, q and k of one head size, H a multiple of Hkv, and S a
whole number of ``block`` tiles.  Head sizes that are not multiples of 128,
or differ between q/k and v, are padded inside the kernel
(``kernel.padded_head_dim``).  :func:`kernel_block` is the tile the shapes
give and :func:`kernel_fits` the rule a caller uses to choose this kernel
over a plain jnp path.
"""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels import interpret_mode
from repro.kernels.attention.kernel import (
    LANES,
    causal_attention_pallas,
    padded_head_dim,
)

MAX_BLOCK = 512
MAX_HEAD_DIM = 256  # the largest padded head size the kernel is chosen for


def kernel_block(seq: int) -> int:
    """The query and key/value tile for a sequence of ``seq`` tokens."""
    return min(MAX_BLOCK, seq)


def kernel_fits(seq: int, head_dim: int, v_head_dim: int | None = None) -> bool:
    """Whether the kernel takes these shapes: ``seq`` a whole number of
    :func:`kernel_block` tiles, each a multiple of 128 lanes, and the query/key
    and value head sizes padded to at most ``MAX_HEAD_DIM``."""
    block = kernel_block(seq)
    dv = head_dim if v_head_dim is None else v_head_dim
    return (seq % block == 0 and block % LANES == 0
            and padded_head_dim(head_dim, dv) <= MAX_HEAD_DIM)


def causal_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                     block: int | None = None,
                     interpret: bool | None = None) -> jax.Array:
    """Causal self-attention, q (B, S, H, Dqk) over k (B, S, Hkv, Dqk) and
    v (B, S, Hkv, Dv); returns (B, S, H, Dv).

    Query head ``h`` reads key/value head ``h // (H // Hkv)``; position
    ``i`` attends to ``0..i``; the scale is ``1/sqrt(Dqk)``.  Differentiable
    in q, k and v.  ``block`` defaults to :func:`kernel_block` of S."""
    if q.ndim != 4 or k.ndim != 4 or v.shape[:3] != k.shape[:3]:
        raise ValueError(f"expected q (B,S,H,Dqk), k (B,S,Hkv,Dqk) and v "
                         f"(B,S,Hkv,Dv), got {q.shape}, {k.shape}, {v.shape}")
    (b, s, h, d), (bk, sk, hkv, dk) = q.shape, k.shape
    if (b, s, d) != (bk, sk, dk) or h % hkv:
        raise ValueError(f"q {q.shape} and k {k.shape} disagree on batch, "
                         f"sequence or head size, or {h} heads are not a "
                         f"multiple of {hkv} key/value heads")
    if not q.dtype == k.dtype == v.dtype:
        raise ValueError(f"q, k, v dtypes differ: {q.dtype}, {k.dtype}, {v.dtype}")
    block = kernel_block(s) if block is None else block
    if s % block or block % LANES:
        raise ValueError(f"sequence {s} must be a whole number of {block}-token "
                         f"blocks, and the block ({block}) a multiple of "
                         f"{LANES}")
    return _causal_attention(q, k, v, block, interpret_mode(interpret))


@partial(jax.jit, static_argnames=("block", "interpret"))
def _causal_attention(q, k, v, block, interpret):
    return causal_attention_pallas(q, k, v, block=block, interpret=interpret)
