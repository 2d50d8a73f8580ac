"""Sparse embedding push: the PS key-value insight applied to recsys tables.

Baseline (pbox over the full chunk space) treats the 24B-row embedding
tables as dense parameters: the push reduce-scatters gigabytes of mostly
zero gradient.  The paper's PS is a *key-value* store precisely because
embedding-style workloads touch a tiny key subset per step; this module
routes table gradients as (ids, cotangent-rows) pairs instead:

  1. the loss is differentiated w.r.t. the *post-lookup* embeddings ``e``
     (the dense interaction stage's input), giving cot_e (B_w/tp, F, D);
  2. cot_e is all-gathered over the model axis (the manual transpose of the
     lookup's psum_scatter) -> (B_w, F, D), cast to bf16 (wire dtype);
  3. ids + cotangents are all-gathered over the worker axes — total wire
     bytes = global_batch x F x (D x 2 + 4), independent of table size:
     for dlrm train_batch that is ~0.4 GB/device vs ~12 GB dense;
  4. each table shard scatter-adds the rows it owns with the SGD step fused
     into the scatter (sparse/"lazy" update semantics, the MLPerf DLRM
     convention) — no dense table gradient is ever materialized.

Dense (bot/top MLP) parameters still flow through the chunked PBox exchange.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P


from repro.core.exchange import PSExchange
from repro.models.common import Dist
from repro.runtime.trainer import apply_grad_sync, local_template


def coalesce_ids_rows(ids: Any, rows: jax.Array) -> tuple[np.ndarray,
                                                          jax.Array]:
    """NIC-side duplicate-id coalescing: ``(ids (n,), rows (n, D))`` ->
    ``(unique ascending ids, per-id summed rows)``.

    A batch that touches row 7 five times routes *one* wire row carrying
    the sum — the key-value dedup the PS push exists for.  The reduction
    is a segment-sum (duplicates fold in batch order), computed *before*
    any routing decision, so the summed bits are independent of how the
    table is sharded; core/sparse.SparseTier leans on that for its
    bit-identity invariant."""
    ids_np = np.asarray(ids).reshape(-1)
    rows = jnp.asarray(rows, jnp.float32)
    if rows.shape[0] != ids_np.size:
        raise ValueError(
            f"rows leading dim {rows.shape[0]} != {ids_np.size} ids")
    if ids_np.size == 0:
        return ids_np.astype(np.int64), rows
    uniq, inv = np.unique(ids_np, return_inverse=True)
    summed = jax.ops.segment_sum(rows, jnp.asarray(inv),
                                 num_segments=int(uniq.size))
    return uniq.astype(np.int64), summed


def sparse_table_update(
    tables: dict,  # name -> (V_loc, D) local shard
    ids: jax.Array,  # (B_w, F) this worker's ids (global)
    cot_e: jax.Array,  # (B_w/tp, F, D) cotangent at the lookup output
    dist: Dist,
    worker_axes,
    lr: jax.Array | float,
    wire_dtype=jnp.bfloat16,
) -> dict:
    """Apply one sparse SGD step to every table shard. Per-device code."""
    # (2) undo the batch split: full worker cotangents on every model shard
    if dist.model_axis is not None:
        cot = lax.all_gather(cot_e, dist.model_axis, axis=0, tiled=True)
    else:
        cot = cot_e
    cot = cot.astype(wire_dtype)
    # (3) one round over workers: ids + cotangent rows (global batch)
    if worker_axes:
        ids_all = lax.all_gather(ids, worker_axes, axis=0, tiled=True)
        cot_all = lax.all_gather(cot, worker_axes, axis=0, tiled=True)
        nw = 1
        for a in worker_axes:
            nw *= lax.axis_size(a)
    else:
        ids_all, cot_all, nw = ids, cot, 1
    scale = jnp.asarray(lr, jnp.float32) / nw
    midx = dist.model_index()
    new_tables = {}
    for i, (name, t) in enumerate(sorted(tables.items(),
                                         key=lambda kv: int(kv[0][1:]))):
        vloc = t.shape[0]
        local = ids_all[:, i] - midx * vloc
        ok = (local >= 0) & (local < vloc)
        rows = jnp.where(ok, local, 0)
        upd = cot_all[:, i].astype(jnp.float32) * jnp.where(ok, scale, 0.0)[:, None]
        # (4) fused sparse SGD: rows this shard owns, one scatter-add
        new_tables[name] = t.at[rows].add(-upd.astype(t.dtype))
    return new_tables


def make_sparse_recsys_train_step(
    mesh,
    *,
    lookup_fn: Callable,  # (tables, batch, dist) -> e
    loss_from_emb: Callable,  # (dense_params, e, batch, dist) -> (loss, met)
    dense_specs: Any,
    dense_sync: Any,
    dense_template: Any,  # global ShapeDtypeStructs for the dense params
    table_specs: Any,
    exchange: PSExchange,  # dense-parameter exchange
    dist: Dist,
    batch_spec: Any,
    table_lr: float = 1e-2,
):
    """Returns (jitted step, space, sspecs).

    step(pflat, slots, ef, step_cnt, tables, batch) ->
        (pflat', slots', ef', step', tables', metrics)
    """
    tp = dist.tp if dist.model_axis is not None else 1
    wa = exchange.worker_axes
    local = local_template(dense_template, dense_specs, mesh)
    space = exchange.build_space(local, dict(mesh.shape))
    n_state = exchange.spec.num_state_slots

    def device_step(pflat, slots, ef, step_cnt, tables, batch):
        pf = pflat.reshape(-1)
        slots_l = tuple(s.reshape(-1) for s in slots)
        dense = space.unflatten(pf)
        e = lookup_fn(tables, batch, dist)

        def lf(dense_, e_):
            loss, met = loss_from_emb(dense_, e_, batch, dist)
            return loss, (loss, met)

        (_, (loss, met)), (g_dense, g_e) = jax.value_and_grad(
            lf, argnums=(0, 1), has_aux=True)(dense, e)
        g_dense = apply_grad_sync(g_dense, dense_sync, dist)
        gflat = space.flatten(g_dense, jnp.float32)
        state = {"slots": slots_l, "ef": None, "step": step_cnt}
        new_pf, new_state = exchange.device_update(gflat, pf, state)
        new_tables = sparse_table_update(
            tables, batch["sparse"], g_e, dist, wa, table_lr)
        all_axes = tuple(mesh.axis_names)
        met = jax.tree.map(lambda m: lax.pmean(m, all_axes), met)
        loss = lax.pmean(loss, all_axes)
        return (new_pf.reshape(1, -1),
                tuple(s.reshape(1, -1) for s in new_state["slots"]),
                None, new_state["step"], new_tables,
                {"loss": loss, **met})

    owner = P("model", exchange.owner_axes) if exchange.owner_axes else P("model", None)
    sspecs = {
        "pflat": P("model", None),
        "slots": tuple(owner for _ in range(n_state)),
        "ef": None,
        "step": P(),
    }
    in_specs = (sspecs["pflat"], sspecs["slots"], None, P(), table_specs,
                batch_spec)
    out_specs = (sspecs["pflat"], sspecs["slots"], None, P(), table_specs, P())
    shmap = jax.shard_map(device_step, mesh=mesh, in_specs=in_specs,
                          out_specs=out_specs, check_vma=False)
    return jax.jit(shmap, donate_argnums=(0, 1, 4)), space, sspecs
