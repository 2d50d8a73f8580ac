"""Causal flash attention — the LM step's attention kernel (docs/kernels.md).

``causal_attention(q, k, v)`` is softmax attention of each position over
itself and the positions before it, computed blockwise with the scores in
VMEM (the flash-attention kernels shipped with JAX) and a blockwise
backward.  ``models/transformer._attention`` calls it on a TPU where the
shapes tile (:func:`kernel_fits`), and keeps its jnp path elsewhere.
"""
from repro.kernels.attention.ops import causal_attention, kernel_block, kernel_fits

__all__ = ["causal_attention", "kernel_block", "kernel_fits"]
