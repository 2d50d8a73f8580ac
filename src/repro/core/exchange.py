"""PS parameter exchange: push → aggregate → optimize → pull, per-device SPMD.

These functions are *per-device code*: they must be called inside a fully
manual ``jax.shard_map`` whose mesh carries the worker axes.  Three
strategies, matching the paper's comparison set:

  allreduce   The sharded-baseline data flow: gradients are all-reduced so
              every worker holds the aggregate, and every worker redundantly
              runs the optimizer on the full (local) parameter space.  This
              is what MXNet-style colocated/sharded PS degenerate to in
              collective form, and is the paper's normalization baseline.

  pbox        The PBox/PHub design: the flat chunk space is owned in equal
              slabs by every worker (micro-shards).  Push = one
              reduce-scatter (aggregation happens *in the interconnect* —
              on a TPU the ICI reduction is literally the paper's §3
              in-network aggregation); optimize = fused Pallas kernel on the
              owned slab only (PHub's fused aggregator+optimizer, zero
              cross-core synchronization); pull = one all-gather.  One round
              of communication, minimum total bytes, balanced by
              construction — the three properties §2 claims for PHub.

  pbox_hier   The paper's Fig. 5 hybrid/hierarchical scheme: aggregate
              *within* a pod first (rack-local reduce-scatter), then
              exchange only the already-scattered 1/n_data-size slab across
              pods ("a single aggregated stream ... to higher level
              switches"), optionally int8-compressed (switches do integer
              math).  Owners are the pod-local data axis; optimizer state is
              replicated across pods, and the pull never crosses pods.

All strategies share identical update semantics (tested equal to the
reference optimizer): they differ only in where bytes move — which is the
paper's thesis.  Each stage runs under a ``jax.named_scope`` (``push``,
``apply``, ``pull``), so a profile of the compiled step names its device
work by stage.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import compression as comp
from repro.core.chunking import ParamSpace
from repro.core.compression import CompressionConfig
from repro.kernels.fused_agg_opt.ops import fused_aggregate_update
from repro.optim.optimizers import OptimizerSpec


@dataclasses.dataclass(frozen=True)
class ExchangeConfig:
    strategy: str = "pbox"  # "allreduce" | "pbox" | "pbox_hier"
    chunk_elems: int = 8192
    compression: CompressionConfig = CompressionConfig()
    pull_dtype: Any = None  # e.g. jnp.bfloat16 to halve pull bytes
    # use_pallas=True applies the slab update with the fused_agg_opt kernel
    # (compiled on a TPU, interpreted elsewhere); the default jnp path is
    # numerically identical (tests/test_kernels.py) and XLA fuses it into
    # the same single-pass update.  Which is faster on the chip is open.
    use_pallas: bool = False


class PSExchange:
    """Binds (optimizer, exchange config, mesh axis roles).

    ``worker_axes``: mesh axes over which gradients differ (batch sharding).
    ``pod_axis``: the outermost worker axis treated as the "rack" boundary
    for the hierarchical strategy (must be first in worker_axes).
    """

    def __init__(
        self,
        spec: OptimizerSpec,
        cfg: ExchangeConfig,
        worker_axes: Sequence[str],
        pod_axis: str | None = None,
    ):
        self.spec = spec
        self.cfg = cfg
        self.worker_axes = tuple(worker_axes)
        self.pod_axis = pod_axis
        if cfg.strategy == "pbox_hier":
            if pod_axis is None or pod_axis != self.worker_axes[0]:
                raise ValueError(
                    "pbox_hier requires pod_axis == worker_axes[0], got "
                    f"{pod_axis} vs {self.worker_axes}"
                )
            self.owner_axes = self.worker_axes[1:]
        elif cfg.strategy == "pbox":
            self.owner_axes = self.worker_axes
        elif cfg.strategy == "allreduce":
            self.owner_axes = ()
        else:
            raise ValueError(f"unknown strategy {cfg.strategy}")

    # ------------------------------------------------------------------
    # layout helpers (host side)
    # ------------------------------------------------------------------
    def build_space(self, local_params: Any, mesh_axis_sizes: dict) -> ParamSpace:
        """ParamSpace over the *local* (model-sharded) tensor shapes."""
        n_owners = 1
        for a in self.owner_axes:
            n_owners *= mesh_axis_sizes[a]
        return ParamSpace.build(
            local_params, chunk_elems=self.cfg.chunk_elems, num_owners=max(n_owners, 1)
        )

    def slab_elems(self, space: ParamSpace) -> int:
        if self.cfg.strategy == "allreduce":
            return space.flat_elems
        return space.flat_elems // space.num_owners

    def init_slab_state(self, space: ParamSpace) -> dict:
        """Per-device optimizer + error-feedback state (slab sized)."""
        n = self.slab_elems(space)
        slots = tuple(
            jnp.zeros((n,), jnp.float32) for _ in range(self.spec.num_state_slots)
        )
        ef = comp.init_ef_state(self.cfg.compression, n)
        return {"slots": slots, "ef": ef, "step": jnp.zeros((), jnp.int32)}

    # ------------------------------------------------------------------
    # per-device exchange (call inside shard_map)
    # ------------------------------------------------------------------
    def _num_workers(self) -> Any:
        n = 1
        for a in self.worker_axes:
            n *= lax.axis_size(a)
        return n

    def device_update(
        self,
        gflat: jax.Array,  # (flat,) local-model-shard gradient, f32
        pflat: jax.Array,  # (flat,) local-model-shard params (PS dtype)
        state: dict,  # from init_slab_state
        lr_scale: jax.Array | float = 1.0,
    ) -> tuple[jax.Array, dict]:
        """One PS round.  Returns (new pflat, new state)."""
        cfg, spec = self.cfg, self.spec
        step = state["step"] + 1
        nw = self._num_workers()

        if cfg.strategy == "allreduce":
            with jax.named_scope("push"):
                g = lax.psum(gflat, self.worker_axes) / nw
            with jax.named_scope("apply"):
                new_p, new_slots = fused_aggregate_update(
                    g[None],
                    pflat,
                    state["slots"],
                    spec,
                    step,
                    lr_scale,
                    average=False,
                    use_pallas=cfg.use_pallas,
                )
            return new_p, {"slots": new_slots, "ef": state["ef"], "step": step}

        if cfg.strategy == "pbox":
            # push: one reduce-scatter over all worker axes (aggregation on
            # the wire), arriving already summed at the chunk owner.
            with jax.named_scope("push"):
                slab = lax.psum_scatter(
                    gflat, self.worker_axes, scatter_dimension=0, tiled=True
                )
                slab = slab / nw
            with jax.named_scope("apply"):
                widx = lax.axis_index(self.worker_axes)
                n = slab.shape[0]
                pslab = lax.dynamic_slice_in_dim(pflat, widx * n, n)
                new_slab, new_slots = fused_aggregate_update(
                    slab[None],
                    pslab,
                    state["slots"],
                    spec,
                    step,
                    lr_scale,
                    average=False,
                    use_pallas=cfg.use_pallas,
                )
            # pull: one all-gather of updated slabs
            with jax.named_scope("pull"):
                pulled = new_slab
                if cfg.pull_dtype is not None:
                    pulled = pulled.astype(cfg.pull_dtype)
                new_p = lax.all_gather(pulled, self.worker_axes, axis=0,
                                       tiled=True)
                new_p = new_p.astype(pflat.dtype)
            return new_p, {"slots": new_slots, "ef": state["ef"], "step": step}

        if cfg.strategy == "pbox_hier":
            pod = self.pod_axis
            data_axes = self.owner_axes
            n_data = 1
            for a in data_axes:
                n_data *= lax.axis_size(a)
            n_pod = lax.axis_size(pod)
            with jax.named_scope("push"):
                # stage 1: rack-local aggregation (reduce-scatter within pod)
                slab = lax.psum_scatter(
                    gflat, data_axes, scatter_dimension=0, tiled=True
                )
                slab = slab / nw
                # stage 2: single aggregated stream across pods, optionally
                # int8
                ef = state["ef"]
                if cfg.compression.codec == "none":
                    slab = lax.psum(slab, pod)
                else:
                    payload, ef = comp.encode(cfg.compression, slab, ef)
                    # integer aggregation across pods: gather peers'
                    # compressed payloads, decode, and sum locally (models
                    # switch-side integer adds with per-chunk rescale).
                    gathered = tuple(
                        lax.all_gather(p, pod, axis=0, tiled=False)
                        for p in payload
                    )
                    parts = [
                        comp.decode(cfg.compression,
                                    tuple(g[i] for g in gathered))
                        for i in range(n_pod)
                    ]
                    slab = jnp.sum(jnp.stack(parts), axis=0)
            with jax.named_scope("apply"):
                widx = lax.axis_index(data_axes)
                n = slab.shape[0]
                pslab = lax.dynamic_slice_in_dim(pflat, widx * n, n)
                new_slab, new_slots = fused_aggregate_update(
                    slab[None],
                    pslab,
                    state["slots"],
                    spec,
                    step,
                    lr_scale,
                    average=False,
                    use_pallas=cfg.use_pallas,
                )
            # pull stays inside the pod: updates are replicated across pods
            with jax.named_scope("pull"):
                pulled = new_slab
                if cfg.pull_dtype is not None:
                    pulled = pulled.astype(cfg.pull_dtype)
                new_p = lax.all_gather(pulled, data_axes, axis=0, tiled=True)
                new_p = new_p.astype(pflat.dtype)
            return new_p, {"slots": new_slots, "ef": ef, "step": step}

        raise ValueError(cfg.strategy)

    # ------------------------------------------------------------------
    # analytical wire-byte model (used by benchmarks + roofline narrative)
    # ------------------------------------------------------------------
    def modeled_bytes(self, flat_elems: int, n_pod: int, n_data: int) -> dict:
        """Per-device bytes moved per step, by stage (f32 grads).

        "allreduce" here models the paper's *colocated sharded PS* baseline
        (Fig. 3's normalization): every worker ships the full gradient to
        the PS shards and pulls full parameters back, while its own NIC
        simultaneously serves its PS shard's aggregate traffic — the
        hot link carries ~2x (push+pull) twice. PBox moves the
        collective-theoretic minimum (one RS + one AG) on balanced links."""
        G = flat_elems * 4
        nw = n_pod * n_data
        c = self.cfg.compression.wire_bytes_per_elem / 4.0
        pull = self.cfg.pull_dtype is not None and 0.5 or 1.0
        if self.cfg.strategy == "allreduce":
            # colocated sharded PS: worker traffic (2G) + shard-serving
            # traffic (2G * (nw-1)/nw) on the same link
            return {"push": 2 * G + 2 * G * (nw - 1) / nw, "pull": 0.0,
                    "xpod": None}
        if self.cfg.strategy == "pbox":
            # RS: G*(nw-1)/nw out; AG: same back
            s = G * (nw - 1) / nw
            return {"push": s, "pull": s * pull, "xpod": None}
        if self.cfg.strategy == "pbox_hier":
            s = G * (n_data - 1) / n_data  # intra-pod RS + AG
            x = (G / n_data) * 2 * (n_pod - 1) / n_pod * c  # cross-pod AR
            return {"push": s, "pull": s * pull, "xpod": x}
        raise ValueError(self.cfg.strategy)
