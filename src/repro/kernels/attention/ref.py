"""Pure-jnp oracle for causal self-attention.

The plain statement of what the kernel computes: scores from the inputs'
dtype accumulated in f32, the ``1/sqrt(Dqk)`` scale and the softmax in
f32, the probabilities rounded to the inputs' dtype for the product with
``v``.  The whole ``S x S`` score matrix is materialized, which is what the
kernel exists to avoid.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def causal_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array) -> jax.Array:
    """Causal attention of ``q`` (B, S, H, Dqk) over ``k`` (B, S, Hkv, Dqk)
    and ``v`` (B, S, Hkv, Dv).

    Query head ``h`` reads key/value head ``h // (H // Hkv)`` (grouped-query
    attention); position ``i`` attends to positions ``0..i``.  Returns
    (B, S, H, Dv) in ``q``'s dtype."""
    s, h, d = q.shape[1:]
    group = h // k.shape[2]
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * (1.0 / math.sqrt(d))
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v, preferred_element_type=jnp.float32)
    return out.astype(q.dtype)
