"""Training runtime: the PS train step, assembled inside one shard_map.

Data flow per step (per device):

  pflat (flat chunked params, this model shard)      <- TrainState
    -> unflatten to the model pytree
    -> value_and_grad of the per-device loss (/tp — see transformer.grad_sync)
    -> apply grad-sync tags (psum_model / scale_R for replicated-copy params)
    -> flatten grads into the chunk space                (PHub key chunking)
    -> exchange.device_update: push / fused-update / pull (PBox)
  -> new pflat, new PS state, pmean'd metrics

Keeping parameters *in flat chunked form between steps* is the PHub design
decision: zero re-layout cost at exchange time, checkpoint shards are
chunk-aligned, and elastic re-sharding is a pure reshape (runtime/elastic).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.chunking import ParamSpace
from repro.core.exchange import PSExchange
from repro.core.fabric import ServerStats
from repro.models.common import Dist


@dataclasses.dataclass
class TrainState:
    """Global (host-view) training state."""

    pflat: jax.Array  # (n_groups, flat_local)  — model-axis groups
    slots: tuple  # each (n_groups, flat_local) f32 (sharded over owners)
    ef: jax.Array | None
    step: jax.Array  # scalar int32


def local_template(global_tree: Any, specs: Any, mesh) -> Any:
    """Shrink global ShapeDtypeStructs to per-device local shapes."""

    def shrink(x, spec):
        shape = list(x.shape)
        for i, s in enumerate(spec):
            if s is None:
                continue
            axes = s if isinstance(s, tuple) else (s,)
            for a in axes:
                shape[i] //= mesh.shape[a]
        return jax.ShapeDtypeStruct(tuple(shape), x.dtype)

    return jax.tree.map(shrink, global_tree, specs,
                        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


def apply_grad_sync(grads: Any, tags: Any, dist: Dist) -> Any:
    """Apply per-tensor gradient corrections (see transformer.grad_sync)."""

    def fix(g, tag):
        if tag == "none" or dist.model_axis is None:
            return g
        if tag == "psum_model":
            return lax.psum(g, dist.model_axis)
        if tag.startswith("scale_"):
            return g * float(tag.split("_")[1])
        raise ValueError(f"unknown grad-sync tag {tag}")

    return jax.tree.map(fix, grads, tags)


def attach_telemetry(
    step_fn: Callable,
    exchange: PSExchange,
    space: ParamSpace,
    mesh,
    stats: ServerStats | None = None,
    topology=None,
    job=None,
    replication: int | None = None,
    read_plane=None,
) -> Callable:
    """Wrap a jitted PS train step so every invocation records the modeled
    wire traffic into a fabric-style ``ServerStats``.

    The SPMD path moves bytes inside collectives, so unlike the in-process
    ``PBoxFabric`` there is nothing to count at the host; this uses the
    exchange's analytic wire model (``PSExchange.modeled_bytes``, the same
    model the Fig. 4/5 benchmarks plot) scaled by the worker count, giving
    both PS implementations one accounting surface.

    Pass a ``core/topology.NetworkTopology`` to split the push traffic into
    the two wire tiers the fabric tracks: every worker stream crosses its
    rack link, while the oversubscribed core link carries one
    codec-compressed stream per rack when ToR aggregation is on (or every
    worker stream when it is off) — the same codec-exact byte model
    (``compression.wire_bytes``) the fabric uses.

    Pass a tenancy ``JobHandle`` as ``job`` to default ``stats``,
    ``topology`` and ``replication`` from the job — the SPMD step's
    modeled traffic then lands in that tenant's per-job ``ServerStats``
    on the shared box.

    ``replication`` models the fault tier's chain traffic
    (core/replication.py) on this accounting surface too: each step ships
    ``R - 1`` raw-f32 state streams (params + optimizer slots — state
    replication is never lossy) into ``bytes_replication``, crossing the
    core when the topology's anti-affine placement puts backups in other
    racks.

    Pass a ``core/serving.ReadPlane`` as ``read_plane`` to keep a
    snapshot-backed serving tier's round clock in sync with SPMD training:
    each step calls ``read_plane.notify_round()``, so reads served between
    checkpoint publishes report their true staleness (the in-process
    fabric path needs no hook — its planes read the live round counter)."""
    from repro.core.compression import wire_bytes as _wire_bytes

    if job is not None:
        stats = job.stats if stats is None else stats
        topology = job.topology if topology is None else topology
        if replication is None:
            replication = getattr(job, "replication", None)
    replication = 1 if replication is None else replication
    if replication < 1:
        raise ValueError("replication factor must be >= 1")
    if stats is None:
        raise ValueError("attach_telemetry needs stats= or job=")
    n_pod = mesh.shape[exchange.pod_axis] if exchange.pod_axis else 1
    n_workers = 1
    for a in exchange.worker_axes:
        n_workers *= mesh.shape[a]
    if topology is not None and topology.num_workers != n_workers:
        raise ValueError(
            f"topology is for {topology.num_workers} workers, mesh worker "
            f"axes give {n_workers}"
        )
    n_data = n_workers // n_pod
    mb = exchange.modeled_bytes(space.flat_elems, n_pod, n_data)
    push = int(mb["push"] + (mb["xpod"] or 0.0))
    pull = int(mb["pull"])
    # only pbox_hier actually compresses its wire, and only on the
    # cross-pod (core) stage; every strategy's intra-pod push is raw f32,
    # so the rack tier must never claim codec savings the exchange does
    # not realize
    compresses = (exchange.cfg.strategy == "pbox_hier"
                  and exchange.cfg.compression.codec != "none")
    raw_stream = 4 * space.flat_elems
    core_stream = (_wire_bytes(exchange.cfg.compression, space.flat_elems)
                   if compresses else raw_stream)
    if topology is not None:
        rack_bytes = raw_stream * n_workers
        core_streams = (topology.num_racks if topology.rack_aggregation
                        else n_workers)
        core_bytes = core_stream * core_streams
    else:
        rack_bytes = 0
        core_bytes = core_stream * n_workers
    # fault tier: R-1 chain hops per step, each shipping the full slab
    # state raw (params + optimizer slots); anti-affine placement means
    # the hops cross racks whenever there is more than one rack
    repl_stream = 4 * space.flat_elems * (1 + exchange.spec.num_state_slots)
    repl_bytes = repl_stream * (replication - 1)
    repl_cross_rack = topology is not None and topology.num_racks > 1

    def wrapped(*args, **kwargs):
        out = step_fn(*args, **kwargs)
        stats.steps += 1
        stats.pushes += n_workers
        stats.pulls += n_workers
        stats.bytes_pushed += push * n_workers
        stats.bytes_pulled += pull * n_workers
        stats.bytes_rack_link += rack_bytes
        stats.bytes_core_link += core_bytes
        stats.chunk_pushes += space.num_chunks * n_workers
        stats.chunk_pulls += space.num_chunks * n_workers
        if repl_bytes:
            stats.bytes_replication += repl_bytes
            stats.replication_rounds += 1
            if repl_cross_rack:
                stats.bytes_core_link += repl_bytes
            elif topology is not None:
                stats.bytes_rack_link += repl_bytes
        if read_plane is not None:
            read_plane.notify_round()
        return out

    return wrapped


def _state_specs(exchange: PSExchange, n_state: int, has_ef: bool):
    group = "model"
    owner = P(group, exchange.owner_axes) if exchange.owner_axes else P(group, None)
    return {
        "pflat": P(group, None),
        "slots": tuple(owner for _ in range(n_state)),
        "ef": owner if has_ef else None,
        "step": P(),
    }


def make_ps_train_step(
    mesh,
    *,
    loss_fn: Callable,  # (params, batch, dist) -> (loss, metrics); per-device
    param_specs: Any,
    sync_tags: Any,
    global_param_template: Any,  # pytree of ShapeDtypeStruct (global shapes)
    exchange: PSExchange,
    dist: Dist,
    batch_spec: Any,  # pytree of PartitionSpec for the batch
    ps_dtype=jnp.float32,
    loss_div_tp: bool = True,
    lr_schedule: Callable | None = None,
    donate: bool = True,
    microbatches: int = 1,
):
    """Returns (jitted step, ParamSpace, state_specs, n_groups).

    step(pflat, slots, ef, step_count, batch) ->
        (new_pflat, new_slots, new_ef, new_step, metrics)

    The step's work carries stable ``jax.named_scope`` names: ``fwd`` around
    the loss (so the backward reads ``transpose(jvp(fwd))`` and a
    recomputed forward ``rematted_computation``), ``accumulate`` around the
    gradient's flatten and microbatch sum, the exchange's ``push``,
    ``apply`` and ``pull``, and ``pull`` as well around the pulled
    parameters' layout: their reshape into the state's row and, at the next
    step's start, their unflatten into the model's tree.
    """
    tp = dist.tp if dist.model_axis is not None else 1
    n_groups = tp if dist.model_axis is not None else 1
    local = local_template(global_param_template, param_specs, mesh)
    space = exchange.build_space(local, dict(mesh.shape))
    n_state = exchange.spec.num_state_slots
    has_ef = (
        exchange.cfg.compression.codec != "none"
        and exchange.cfg.compression.error_feedback
    )
    sspecs = _state_specs(exchange, n_state, has_ef)

    def device_step(pflat, slots, ef, step_cnt, batch):
        # the pulled parameters, from the state's row into the model's tree
        with jax.named_scope("pull"):
            pf = pflat.reshape(-1)  # (flat_local,)
            params = space.unflatten(pf)
        slots_l = tuple(s.reshape(-1) for s in slots)
        ef_l = ef.reshape(-1) if ef is not None else None

        def grads_of(mb):
            def lf_tree(params_):
                with jax.named_scope("fwd"):
                    loss, met = loss_fn(params_, mb, dist)
                lossd = loss / tp if (loss_div_tp and tp > 1) else loss
                return lossd, (loss, met)

            (_, (loss, met)), grads = jax.value_and_grad(lf_tree, has_aux=True)(
                params
            )
            grads = apply_grad_sync(grads, sync_tags, dist)
            with jax.named_scope("accumulate"):
                gflat = space.flatten(grads, ps_dtype)
            return gflat, loss, met

        if microbatches <= 1:
            gflat, loss, met = grads_of(batch)
        else:
            # gradient accumulation: one PS exchange per global batch
            mbs = jax.tree.map(
                lambda x: x.reshape(microbatches, x.shape[0] // microbatches,
                                    *x.shape[1:]),
                batch,
            )

            def body(acc, mb):
                g, loss, met = grads_of(mb)
                with jax.named_scope("accumulate"):
                    acc = acc + g
                return acc, (loss, met)

            gflat, (losses, mets) = lax.scan(
                body, jnp.zeros((space.flat_elems,), ps_dtype), mbs
            )
            with jax.named_scope("accumulate"):
                gflat = gflat / microbatches
            loss = jnp.mean(losses)
            met = jax.tree.map(jnp.mean, mets)

        lr_scale = lr_schedule(step_cnt + 1) if lr_schedule is not None else 1.0
        state = {"slots": slots_l, "ef": ef_l, "step": step_cnt}
        new_pf, new_state = exchange.device_update(gflat, pf, state, lr_scale)
        # metrics: mean over every axis (values may vary over worker axes and,
        # for batch-resharding models, over the model axis too)
        all_axes = tuple(mesh.axis_names)
        met = jax.tree.map(lambda m: lax.pmean(m, all_axes), met)
        loss = lax.pmean(loss, all_axes)
        new_slots = tuple(s.reshape(1, -1) for s in new_state["slots"])
        new_ef = (
            new_state["ef"].reshape(1, -1) if new_state["ef"] is not None else None
        )
        with jax.named_scope("pull"):
            new_pf = new_pf.reshape(1, -1)
        return (
            new_pf,
            new_slots,
            new_ef,
            new_state["step"],
            {"loss": loss, **met},
        )

    in_specs = (
        sspecs["pflat"],
        sspecs["slots"],
        sspecs["ef"],
        sspecs["step"],
        batch_spec,
    )
    out_specs = (
        sspecs["pflat"],
        sspecs["slots"],
        sspecs["ef"],
        sspecs["step"],
        P(),
    )
    shmap = jax.shard_map(
        device_step, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )
    jit_kwargs = {"donate_argnums": (0, 1, 2)} if donate else {}
    step = jax.jit(shmap, **jit_kwargs)
    return step, space, sspecs, n_groups


def init_train_state(
    mesh,
    *,
    init_params_fn: Callable,  # (key) -> global param pytree (concrete)
    param_specs: Any,
    exchange: PSExchange,
    space: ParamSpace,
    n_groups: int,
    key,
    ps_dtype=jnp.float32,
) -> TrainState:
    """Build a concrete, correctly-sharded TrainState on the mesh.

    The flat param buffer is assembled per model group by flattening the
    *local shard* of each tensor (host-side loop; fine up to multi-B params
    on a real host, and smoke-scale here)."""
    params = init_params_fn(key)
    groups = []
    for g in range(n_groups):
        def take_local(x, spec):
            idx = [slice(None)] * x.ndim
            for i, s in enumerate(spec):
                if s is None:
                    continue
                axes = s if isinstance(s, tuple) else (s,)
                if "model" in axes:
                    n = x.shape[i] // n_groups
                    idx[i] = slice(g * n, (g + 1) * n)
            return x[tuple(idx)]

        local = jax.tree.map(take_local, params, param_specs)
        groups.append(space.flatten(local, ps_dtype))
    pflat = jnp.stack(groups)
    n_state = exchange.spec.num_state_slots
    slots = tuple(
        jnp.zeros((n_groups, space.flat_elems), jnp.float32) for _ in range(n_state)
    )
    has_ef = (
        exchange.cfg.compression.codec != "none"
        and exchange.cfg.compression.error_feedback
    )
    # NB: slots/ef global second dim is flat_elems (= slab * owners)
    ef = jnp.zeros((n_groups, space.flat_elems), jnp.float32) if has_ef else None
    return TrainState(pflat=pflat, slots=slots, ef=ef, step=jnp.zeros((), jnp.int32))


def state_shardings(mesh, sspecs) -> dict:
    return {
        k: (
            NamedSharding(mesh, v)
            if not isinstance(v, tuple)
            else tuple(NamedSharding(mesh, s) for s in v)
        )
        for k, v in sspecs.items()
        if v is not None
    }
