"""Causal flash attention on the TPU: the flash-attention kernels shipped
with JAX (``jax.experimental.pallas.ops.tpu.flash_attention``).

Scores live in VMEM one ``(block, block)`` tile at a time under an online
softmax; tiles above the diagonal are skipped, neither fetched nor
computed.  The backward recomputes each tile's probabilities from
``(q, k, v, o)`` and the forward's row max and sum, in two Pallas passes
(dk/dv, then dq): no ``S x S`` array reaches HBM in either direction.

Layout.  The model's (B, S, H, D) arrays are transposed to the kernel's
(B, H, S, D).  Grouped key/value heads are repeated to one per query head.

Precision.  Scores accumulate in f32 from the inputs' dtype and are scaled
by ``1/sqrt(D)`` in f32; the softmax statistics are f32; the probabilities
(and in the backward the score gradients) are rounded to the inputs' dtype
for their products with ``v``, ``do``, ``k`` and ``q``, which accumulate in
f32.

Blocks.  ``block`` is the query and key/value tile of all three passes; it
must divide S and be a multiple of the 128 lanes, and D must be a multiple
of 128 as well.  The kernels take no interpret flag: off a TPU they run
under Pallas' TPU interpreter (``force_tpu_interpret_mode``).
"""
from __future__ import annotations

import contextlib
import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas.ops.tpu import flash_attention as flash

LANES = 128


def causal_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                            block: int, interpret: bool) -> jax.Array:
    """Causal attention, q (B, S, H, D) and k/v (B, S, Hkv, D) -> (B, S, H, D)."""
    group = q.shape[2] // k.shape[2]
    if group > 1:
        k = jnp.repeat(k, group, axis=2)
        v = jnp.repeat(v, group, axis=2)
    heads_major = lambda x: jnp.swapaxes(x, 1, 2)  # noqa: E731
    out = _flash(heads_major(q), heads_major(k), heads_major(v), block, interpret)
    return heads_major(out)


def _flash_call(q, k, v, block):
    sizes = flash.BlockSizes(
        block_q=block, block_k_major=block, block_k=block, block_b=1,
        block_q_major_dkv=block, block_k_major_dkv=block, block_q_dkv=block,
        block_k_dkv=block, block_q_dq=block, block_k_major_dq=block,
        block_k_dq=block)
    return flash.flash_attention(q, k, v, causal=True,
                                 sm_scale=1.0 / math.sqrt(q.shape[3]),
                                 block_sizes=sizes)


def _mode(interpret: bool):
    return pltpu.force_tpu_interpret_mode() if interpret else contextlib.nullcontext()


# The shipped kernels are a custom VJP whose backward is traced when the
# gradient is taken; this wrapper holds the interpret mode over both halves.
@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash(q, k, v, block, interpret):
    with _mode(interpret):
        return _flash_call(q, k, v, block)


def _flash_fwd(q, k, v, block, interpret):
    with _mode(interpret):
        return jax.vjp(partial(_flash_call, block=block), q, k, v)


def _flash_bwd(block, interpret, pull, g):
    with _mode(interpret):
        return pull(g)


_flash.defvjp(_flash_fwd, _flash_bwd)
