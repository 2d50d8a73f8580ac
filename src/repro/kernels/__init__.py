"""The Pallas kernel tier — see docs/kernels.md for the full map.

Five families, each a ``ref.py`` (pure-jnp oracle) / ``kernel.py`` (Pallas
program) / ``ops.py`` (validated, jit'd public surface) package:

* ``fused_agg_opt`` — K-way gradient aggregation fused with the server
  optimizer (the PHub hot loop);
* ``quant`` — the chunked int8 wire codec (per-chunk f32 scales);
* ``embedding_bag`` — scalar-prefetch embedding gather/reduce for the
  sparse tier;
* ``wire_path`` — single-pass decode + aggregate + optimize over wire-form
  push payloads, bit-identical to the unfused pipeline;
* ``attention`` — causal flash attention for the transformer's training
  step, built on the flash-attention kernels shipped with JAX.

Import from each family's package (``repro.kernels.<family>``); this
package re-exports nothing.  It holds the one rule for how every kernel
runs: compiled on a TPU and in the Pallas interpreter on any other backend
(:func:`interpret_mode`).  A kernel that fails to compile for the TPU
raises; nothing falls back to the interpreter or to ``ref.py``.
"""
from __future__ import annotations

import jax


def interpret_mode(interpret: bool | None = None) -> bool:
    """Whether a Pallas kernel runs in interpret mode.

    ``interpret`` wins where the caller gives it (the TPU compile tests
    pass ``False`` to compile for a described chip from a CPU host);
    ``None`` means interpret exactly when the default backend is not a
    TPU."""
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"
