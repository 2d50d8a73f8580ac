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
by ``1/sqrt(Dqk)`` in f32; the softmax statistics are f32; the probabilities
(and in the backward the score gradients) are rounded to the inputs' dtype
for their products with ``v``, ``do``, ``k`` and ``q``, which accumulate in
f32.

Head sizes.  The shipped kernels take one head size, a multiple of the 128
lanes, for q, k and v alike.  Where the query/key head size ``Dqk`` and the
value head size ``Dv`` differ or are not multiples of 128 (latent attention:
192 against 128), q, k and v are zero-padded to the next common multiple of
128 and the output is sliced back to ``Dv``.  Zero columns add nothing to
the scores, which are scaled by the true ``1/sqrt(Dqk)``, nor to the output
columns that are kept; the padding is work the kernel does for nothing.

Blocks.  ``block`` is the query and key/value tile of all three passes; it
must divide S and be a multiple of the 128 lanes.  The kernels take no
interpret flag: off a TPU they run under Pallas' TPU interpreter
(``force_tpu_interpret_mode``).
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


def padded_head_dim(dqk: int, dv: int) -> int:
    """The head size the kernel runs at: the next multiple of 128 lanes
    that holds both ``dqk`` and ``dv``."""
    return -(-max(dqk, dv) // LANES) * LANES


def causal_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                            block: int, interpret: bool) -> jax.Array:
    """Causal attention, q/k (B, S, H|Hkv, Dqk) and v (B, S, Hkv, Dv) ->
    (B, S, H, Dv)."""
    group = q.shape[2] // k.shape[2]
    if group > 1:
        k = jnp.repeat(k, group, axis=2)
        v = jnp.repeat(v, group, axis=2)
    dqk, dv = q.shape[3], v.shape[3]
    dp = padded_head_dim(dqk, dv)

    def heads_major(x):  # (B, S, H, d) -> (B, H, S, dp)
        x = jnp.swapaxes(x, 1, 2)
        pad = dp - x.shape[3]
        return jnp.pad(x, ((0, 0),) * 3 + ((0, pad),)) if pad else x

    out = _flash(heads_major(q), heads_major(k), heads_major(v), block,
                 1.0 / math.sqrt(dqk), interpret)
    return jnp.swapaxes(out[..., :dv] if dp > dv else out, 1, 2)


def _flash_call(q, k, v, block, scale):
    sizes = flash.BlockSizes(
        block_q=block, block_k_major=block, block_k=block, block_b=1,
        block_q_major_dkv=block, block_k_major_dkv=block, block_q_dkv=block,
        block_k_dkv=block, block_q_dq=block, block_k_major_dq=block,
        block_k_dq=block)
    return flash.flash_attention(q, k, v, causal=True, sm_scale=scale,
                                 block_sizes=sizes)


def _mode(interpret: bool):
    return pltpu.force_tpu_interpret_mode() if interpret else contextlib.nullcontext()


# The shipped kernels are a custom VJP whose backward is traced when the
# gradient is taken; this wrapper holds the interpret mode over both halves.
@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, block, scale, interpret):
    with _mode(interpret):
        return _flash_call(q, k, v, block, scale)


def _flash_fwd(q, k, v, block, scale, interpret):
    with _mode(interpret):
        return jax.vjp(partial(_flash_call, block=block, scale=scale), q, k, v)


def _flash_bwd(block, scale, interpret, pull, g):
    with _mode(interpret):
        return pull(g)


_flash.defvjp(_flash_fwd, _flash_bwd)
