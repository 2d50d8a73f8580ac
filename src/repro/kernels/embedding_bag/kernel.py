"""Pallas TPU embedding-bag via scalar-prefetch row DMA.

The bags' indices and weights are prefetched to SMEM.  The table stays
where it lives (``pl.ANY``: HBM on a TPU); each grid step owns a block of
``BAGS`` bags, issues one DMA per touched row straight from the table into
a VMEM staging buffer, and folds the weighted rows into its (BAGS, D)
output tile — no dense gather is materialized (the TPU-native analogue of
FBGEMM's table-batched embedding access, and of the PS "pull" of only the
rows a worker touches).

Every block the TPU compiler sees is tile-aligned: the output block is
(8, D), the staging buffer is (L, 8, D) and the per-row DMAs address single
rows of it, so no (1, 1) or (1, D) block appears (the compiler refuses
those as not aligned to the (8, 128) tile).

Each bag's sum is the slot-order left fold ``((0 + w0*r0) + w1*r1) + ...``
accumulated in the output tile, the same order as the oracle's einsum.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BAGS = 8  # bags per grid step: one (8, 128)-tile row block of the output


def _bag_kernel(l: int, idx_ref, w_ref, table_ref, o_ref, rows_ref, sem):
    base = pl.program_id(0) * BAGS * l

    def copy(s, r):
        return pltpu.make_async_copy(
            table_ref.at[pl.ds(idx_ref[base + r * l + s], 1)],
            rows_ref.at[s, pl.ds(r, 1)],
            sem,
        )

    def start(s, carry):
        for r in range(BAGS):
            copy(s, r).start()
        return carry

    def wait(s, carry):
        for r in range(BAGS):
            copy(s, r).wait()
        return carry

    jax.lax.fori_loop(0, l, start, 0)
    jax.lax.fori_loop(0, l, wait, 0)
    o_ref[...] = jnp.zeros_like(o_ref)

    def fold(s, carry):
        for r in range(BAGS):
            w = w_ref[base + r * l + s]
            row = rows_ref[s, pl.ds(r, 1), :].astype(jnp.float32)
            o_ref[pl.ds(r, 1), :] += w * row
        return carry

    jax.lax.fori_loop(0, l, fold, 0)


def embedding_bag_pallas(
    table: jax.Array,  # (V, D)
    indices: jax.Array,  # (B, L) int32
    weights: jax.Array,  # (B, L) f32
    mode: str = "sum",
    *,
    interpret: bool,
) -> jax.Array:
    """Pallas embedding-bag: (B, L) index/weight bags over a (V, D) table.

    Indices and weights are scalar-prefetched; grid step ``i`` DMAs the
    ``BAGS * L`` rows of bags ``[i*BAGS, (i+1)*BAGS)`` from the table and
    accumulates ``w * row`` into those bags.  B is padded to a whole number
    of blocks with zero-weight bags of row 0.  "mean" divides by the weight
    sum afterwards.  Callers go through
    :func:`repro.kernels.embedding_bag.ops.embedding_bag`, which validates
    indices first."""
    b, l = indices.shape
    v, d = table.shape
    pad = (-b) % BAGS
    idx = jnp.pad(indices.astype(jnp.int32), ((0, pad), (0, 0)))
    wgt = jnp.pad(weights.astype(jnp.float32), ((0, pad), (0, 0)))
    nb = (b + pad) // BAGS
    out = pl.pallas_call(
        partial(_bag_kernel, l),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(nb,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((BAGS, d), lambda i, idx_ref, w_ref: (i, 0)),
            scratch_shapes=[
                pltpu.VMEM((l, BAGS, d), table.dtype),
                pltpu.SemaphoreType.DMA(()),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((nb * BAGS, d), jnp.float32),
        interpret=interpret,
    )(idx.reshape(-1), wgt.reshape(-1), table)[:b]
    if mode == "mean":
        denom = jnp.maximum(jnp.sum(weights, axis=1, keepdims=True), 1e-9)
        out = out / denom
    return out
