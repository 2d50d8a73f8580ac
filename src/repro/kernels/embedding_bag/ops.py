"""Validated wrapper for embedding-bag: Pallas kernel or XLA-gather fallback.

The XLA path (take + einsum) is what the distributed lowering uses (XLA
SPMD partitions the gather against row-sharded tables); the Pallas path is
the single-chip TPU kernel.  Both satisfy the same oracle (ref.py).

Validation contract: the Pallas kernel's scalar-prefetch index_map streams
whatever table row the index names — an out-of-range index used to read
garbage (or trap) silently, and a float index would be reinterpreted.  The
wrapper therefore rejects non-integer index dtypes always, checks bounds
eagerly when the indices are concrete, and clamps into ``[0, V)`` before
dispatch so traced callers (inside jit/vmap, where values are unknowable)
get gather-clip semantics — the same convention as
``models/recsys/embedding.lookup_fields``.  Callers that need rejection
under tracing validate at the trace boundary (core/sparse.check_jagged).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import interpret_mode
from repro.kernels.embedding_bag.kernel import embedding_bag_pallas
from repro.kernels.embedding_bag.ref import embedding_bag_ref


@partial(jax.jit, static_argnames=("mode", "use_pallas", "interpret"))
def _dispatch(
    table: jax.Array,
    indices: jax.Array,
    weights: jax.Array,
    mode: str,
    use_pallas: bool,
    interpret: bool | None,
) -> jax.Array:
    indices = jnp.clip(indices, 0, table.shape[0] - 1)
    if use_pallas:
        return embedding_bag_pallas(table, indices, weights, mode,
                                    interpret=interpret_mode(interpret))
    return embedding_bag_ref(table, indices, weights, mode)


def embedding_bag(
    table: jax.Array,
    indices: jax.Array,
    weights: jax.Array,
    mode: str = "sum",
    *,
    use_pallas: bool = False,
    interpret: bool | None = None,
) -> jax.Array:
    """Weighted embedding-bag lookup: bags of table rows, summed or meaned.

    Bag ``b`` returns ``sum_l weights[b, l] * table[indices[b, l]]``
    (``mode="mean"`` divides by the weight sum; pad slots carry weight
    0.0).  Validates ``mode``, integer dtype, and — for concrete indices —
    table range before dispatching to the Pallas kernel
    (``use_pallas=True``) or the jnp reference."""
    if mode not in ("sum", "mean"):
        raise ValueError(f"mode must be 'sum' or 'mean', got {mode!r}")
    if not jnp.issubdtype(jnp.asarray(indices).dtype, jnp.integer):
        raise TypeError(
            f"embedding_bag indices must be integers, got "
            f"{jnp.asarray(indices).dtype} — a float index would be "
            "reinterpreted as a row number")
    if not isinstance(indices, jax.core.Tracer):
        idx = np.asarray(indices)
        v = table.shape[0]
        if idx.size and (int(idx.min()) < 0 or int(idx.max()) >= v):
            raise ValueError(
                f"embedding_bag indices [{int(idx.min())}, "
                f"{int(idx.max())}] out of range for a {v}-row table — "
                "the kernel would silently stream the wrong rows")
    return _dispatch(table, indices, weights, mode, use_pallas, interpret)
