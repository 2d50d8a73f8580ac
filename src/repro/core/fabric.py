"""Chunk-sharded PBox fabric: the paper's balanced multi-engine PS.

PBox's central claim (§3) is that a balanced parameter server must (a) shard
the flat chunked parameter space over multiple aggregation engines, (b) keep
every engine's slab the same size, and (c) overlap the wire with per-chunk
aggregation — chunk *i* is aggregated+optimized while chunk *i+1* is still in
flight.  The previous in-process simulator (``PHubServer``) modelled a single
monolithic engine over the whole flat space; this module replaces it:

  ``PBoxShard``    one aggregation engine.  Owns a set of 32 KB key chunks
                   (initially a contiguous slab), holds their parameters and
                   optimizer state, and runs the *actual* K-way fused
                   aggregate+optimize Pallas kernel on only its slab.

  ``PBoxFabric``   the fabric: routes per-chunk pushes/pulls to the owning
                   shard, enforces sync / async / SSP admission and the
                   backup-worker partial quorum, and can rebalance chunk
                   ownership away from slow shards
                   (runtime/straggler.ShardRebalancer drives this hook).

Numerics are *identical* to the single-server path by construction: the fused
update is elementwise over the flat space and sums workers in a fixed order,
so applying it slab-by-slab is bit-equal to applying it once over the whole
space (tests/test_fabric.py asserts this for 1, 2 and 8 shards).

Pipelining is modelled with an event-ordered simulator clock rather than
threads: each completed push replays the per-chunk timeline (chunk ``c``
arrives at ``(c+1) * wire_us``; a shard aggregates its chunks in arrival
order, overlapping the wire), and ``ServerStats`` records both the pipelined
makespan and the monolithic store-and-forward baseline so benchmarks can plot
shard-count scaling curves.

The fabric is topology- and codec-aware (core/topology.py,
core/compression.py): attach a ``NetworkTopology`` and each rack's worker
pushes are combined at the ToR before crossing the oversubscribed core link
— cross-rack bytes drop ~workers-per-rack, and an integer codec shrinks
them a further ~4x (the paper's in-network-aggregation direction).  With
``codec="none"`` the rack tier chains partial sums in ascending worker
order, so rack-aggregated sync training stays *bit-identical* to the flat
fabric (see core/topology.py's determinism note).  Byte accounting and the
event clock split into a rack-link tier (full bisection) and a core-link
tier (oversubscribed, codec-scaled).

Backup-quorum semantics: every push carries the params version the worker
last pulled; a sync-mode push computed against an already-superseded
version is dropped at admission (counted in
``ServerStats.late_pushes_dropped``), matching the documented policy in
runtime/straggler.py — stale gradients never contaminate the next round's
quorum, while a straggler that re-pulls contributes its fresh gradients.

Multi-tenancy (core/tenancy.py): a ``MultiJobFabric`` runs many jobs'
fabrics over one shared shard set and wire — ``namespace``/``chunk_base``
place this fabric's chunks in the box-wide namespace, and ``shared_clock``
inflates its wire stages for co-tenant contention (weighted fair sharing).
Both hooks are timing/metadata only: a tenant's training stays
bit-identical to a dedicated fabric.

Fault tolerance (core/replication.py): ``replication=R`` chain-replicates
every shard's slab (params + optimizer state, raw f32) to R-1 backups
after each round, placed anti-affine to racks; a ``FaultPlan`` injects
shard/worker/link faults deterministically at round edges on the event
clock.  A shard crash with R >= 2 promotes the chain head bit-exactly and
re-silvers the chain (pushes/pulls re-target the replacement
transparently); with R = 1 it raises ``ShardLost``.  Worker crashes shrink
the admission barrier to the surviving population and re-enter via
``runtime/elastic.worker_reentry``.  Replication/recovery bytes land in
the same rack/core link accounting as training traffic.

Read plane (core/serving.py): a ``ReadPlane`` serves version-stamped,
staleness-bounded parameter reads from the chain replica *tails* while
training runs — it registers in ``read_planes`` only so ``restore`` can
invalidate its caches; it never writes fabric state, so attaching it
leaves training bit-identical.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.chunking import ParamSpace
from repro.core.compression import (
    CompressionConfig,
    WirePayload,
    encode_wire,
    init_ef_state,
    roundtrip,
    wire_bytes,
)
from repro.core.config import FabricConfig, warn_legacy_call
from repro.core.placement import (
    PlacementPlan,
    PlanDelta,
    chunk_rebalance_delta,
)
from repro.core.replication import FaultPlan, ReplicaGroup, ShardLost
from repro.core.topology import (
    NetworkTopology,
    RackAggregator,
    SwitchCompute,
    group_scale,
    integer_quantize,
)
from repro.kernels.fused_agg_opt.kernel import LANES, SUBLANES
from repro.kernels.fused_agg_opt.ops import fused_aggregate_update
from repro.kernels.wire_path.ops import fused_wire_update, wire_path_supported
from repro.optim.optimizers import OptimizerSpec, init_opt_state

# The fused kernel processes slabs in whole (8 sublane) * 8-row register
# blocks of 128 lanes; shard slabs are padded up to this unit (see
# PBoxShard.apply).
_KERNEL_SLAB_UNIT = SUBLANES * LANES * 8


# ---------------------------------------------------------------------------
# stats
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class ServerStats:
    """Fabric-wide accounting (back-compat superset of the old PHubServer
    stats, plus chunk-granular and event-clock pipeline fields)."""

    steps: int = 0
    pushes: int = 0
    pulls: int = 0
    bytes_pushed: int = 0
    bytes_pulled: int = 0
    partial_aggregations: int = 0
    late_pushes_dropped: int = 0  # stale quorum-round pushes refused
    # chunk-granular accounting
    chunk_pushes: int = 0
    chunk_pulls: int = 0
    rebalances: int = 0
    chunks_moved: int = 0
    # placement / autoscaling tier (core/placement.py, runtime/autoscaler.py)
    rescales: int = 0  # in-place shard-count changes (PBoxFabric.reshard)
    replica_moves: int = 0  # chain copies re-homed by a plan delta
    # topology-tier wire accounting (codec-aware byte counts)
    bytes_rack_link: int = 0  # worker -> ToR, full bisection
    bytes_core_link: int = 0  # streams crossing the oversubscribed core
    rack_streams: int = 0  # aggregated upstream streams shipped
    # fused wire path (kernels/wire_path): rounds whose shard updates
    # consumed wire payloads directly in the single-pass kernel
    fused_wire_rounds: int = 0
    # in-network switch tier (core/topology.SwitchCompute)
    switch_rounds: int = 0  # rounds >= 1 ToR pool aggregated its slab
    switch_fallback_rounds: int = 0  # pool-refused rounds (software path)
    core_switch_rounds: int = 0  # rounds the core pool combined rack streams
    bytes_switch_agg: int = 0  # wire bytes absorbed into switch pools
    bytes_switch_saved: int = 0  # PS-ingress bytes the core pool absorbed
    switch_failures: int = 0
    switch_restores: int = 0
    # event-ordered simulator clock (µs of simulated time, cumulative)
    sim_wire_us: float = 0.0
    sim_core_wire_us: float = 0.0  # oversubscribed core stage (topology)
    sim_agg_us: float = 0.0
    sim_pipelined_us: float = 0.0  # chunk-pipelined, sharded makespan
    sim_serialized_us: float = 0.0  # monolithic store-and-forward baseline
    # fault-tolerance tier (core/replication.py)
    shards_crashed: int = 0
    failovers: int = 0  # shard crashes survived by promoting a backup
    resilvers: int = 0  # replacement backups rebuilt after a failover
    workers_crashed: int = 0
    workers_recovered: int = 0
    link_degrades: int = 0
    replication_rounds: int = 0  # rounds whose chain replication completed
    bytes_replication: int = 0  # raw-f32 state streams down the chains
    bytes_resilver: int = 0  # recovery traffic re-silvering replacements
    sim_replication_us: float = 0.0  # chain pass (off the round's crit path)
    sim_recovery_us: float = 0.0  # event-clock time failovers spent

    @property
    def pipeline_speedup(self) -> float:
        """Simulated speedup of chunk-pipelined sharded aggregation over the
        monolithic push-everything-then-aggregate baseline."""
        if self.sim_pipelined_us <= 0.0:
            return 1.0
        return self.sim_serialized_us / self.sim_pipelined_us


@dataclasses.dataclass
class ShardStats:
    chunk_pushes: int = 0
    chunk_pulls: int = 0
    bytes_pushed: int = 0
    bytes_pulled: int = 0
    agg_events: int = 0
    sim_busy_us: float = 0.0


@dataclasses.dataclass(frozen=True)
class LinkModel:
    """Event-clock costs for the pipelined push/aggregate/pull simulation.

    Workers stream chunks in ascending chunk order on their own links, so
    chunk ``c`` (all workers' copies) lands at ``(c+1) * wire_us_per_chunk``;
    a shard then spends ``agg_us_per_chunk`` of engine time per chunk.

    ``wire_us_per_chunk`` is the cost of a raw f32 chunk on a rack-local
    (full-bisection) link.  The fabric scales it by the codec's wire bytes
    and, when a ``NetworkTopology`` is attached, adds a second pipeline
    stage for the core uplink: per-chunk core time is the rack-link time x
    the topology's oversubscription factor, further multiplied by the
    number of streams sharing the uplink (1 with ToR aggregation; the rack
    population without)."""

    wire_us_per_chunk: float = 1.0
    agg_us_per_chunk: float = 0.5


# ---------------------------------------------------------------------------
# shard
# ---------------------------------------------------------------------------
class PBoxShard:
    """One aggregation engine: owns chunks, runs the fused kernel on them."""

    def __init__(
        self,
        shard_id: int,
        space: ParamSpace,
        spec: OptimizerSpec,
        chunk_ids: np.ndarray,
        chunk_params: jax.Array,  # (n_owned, chunk_elems) f32
        *,
        use_pallas: bool = True,
    ):
        self.shard_id = shard_id
        self.space = space
        self.spec = spec
        self.chunk_ids = np.asarray(chunk_ids, dtype=np.int64)
        self.params = chunk_params.astype(jnp.float32)
        self.state = init_opt_state(spec, self.params)
        self.use_pallas = use_pallas
        self.stats = ShardStats()

    @property
    def num_chunks(self) -> int:
        return len(self.chunk_ids)

    @property
    def num_elems(self) -> int:
        return self.num_chunks * self.space.chunk_elems

    def apply(self, grads: jax.Array, step: int, *, average: bool) -> None:
        """grads: (K, n_owned, chunk_elems) worker gradient rows for this
        shard's chunks, stacked in ascending worker order."""
        if self.num_chunks == 0:
            return
        k = grads.shape[0]
        n = self.num_elems
        # The Pallas kernel wants slabs in whole 8*128*8 vector-register
        # blocks; pad with zero grad/param/state rows (a zero fixed point for
        # every optimizer here), so any chunk count keeps the kernel path —
        # and the same math path — regardless of how chunks are sharded.
        pad = (-n) % _KERNEL_SLAB_UNIT if self.use_pallas else 0
        gf = grads.reshape(k, n)
        pf = self.params.reshape(n)
        sf = tuple(s.reshape(n) for s in self.state)
        if pad:
            gf = jnp.concatenate([gf, jnp.zeros((k, pad), gf.dtype)], axis=1)
            pf = jnp.concatenate([pf, jnp.zeros((pad,), pf.dtype)])
            sf = tuple(jnp.concatenate([s, jnp.zeros((pad,), s.dtype)])
                       for s in sf)
        new_p, new_s = fused_aggregate_update(
            gf,
            pf,
            sf,
            self.spec,
            jnp.int32(step),
            average=average,
            use_pallas=self.use_pallas,
        )
        shape = (self.num_chunks, self.space.chunk_elems)
        self.params = new_p[:n].reshape(shape)
        self.state = tuple(s[:n].reshape(shape) for s in new_s)
        self.stats.agg_events += 1

    def apply_wire(
        self,
        payload: jax.Array,  # (K, n_owned, chunk_elems) wire dtype
        scales: jax.Array | None,  # (K, n_owned) f32 (int8 codec), else None
        codec: str,
        step: int,
        *,
        average: bool,
    ) -> None:
        """``apply``, wire-form: the K streams arrive still encoded and the
        single-pass kernel (kernels/wire_path) dequantizes, folds and
        applies the optimizer without materializing decoded f32 gradients.
        Shard slabs are whole chunks, so no padding is ever needed (the
        kernel blocks on chunk boundaries); bit-parity with decode-then-
        ``apply`` is the kernel's invariant (tests/test_wire_path.py)."""
        if self.num_chunks == 0:
            return
        k = payload.shape[0]
        n = self.num_elems
        new_p, new_s = fused_wire_update(
            payload.reshape(k, n),
            None if scales is None else scales.reshape(k, self.num_chunks),
            self.params.reshape(n),
            tuple(s.reshape(n) for s in self.state),
            self.spec,
            jnp.int32(step),
            codec=codec,
            chunk_elems=self.space.chunk_elems,
            average=average,
        )
        shape = (self.num_chunks, self.space.chunk_elems)
        self.params = new_p.reshape(shape)
        self.state = tuple(s.reshape(shape) for s in new_s)
        self.stats.agg_events += 1

    # -- chunk migration (rebalancing) ---------------------------------
    def release(self, chunk_ids: np.ndarray) -> tuple[jax.Array, tuple]:
        """Give up ownership of ``chunk_ids``; returns their (params, state)
        rows in the order of ``chunk_ids``."""
        pos = np.searchsorted(self.chunk_ids, chunk_ids)
        if np.any(pos >= len(self.chunk_ids)) or not np.array_equal(
                self.chunk_ids[pos], chunk_ids):
            raise ValueError("releasing chunks this shard does not own")
        p_rows = self.params[pos]
        s_rows = tuple(s[pos] for s in self.state)
        keep = np.ones(self.num_chunks, dtype=bool)
        keep[pos] = False
        self.chunk_ids = self.chunk_ids[keep]
        keep_j = jnp.asarray(np.where(keep)[0])
        self.params = self.params[keep_j]
        self.state = tuple(s[keep_j] for s in self.state)
        return p_rows, s_rows

    def adopt(self, chunk_ids: np.ndarray, p_rows: jax.Array, s_rows: tuple) -> None:
        """Take ownership of ``chunk_ids`` with their (params, state) rows."""
        merged = np.concatenate([self.chunk_ids, np.asarray(chunk_ids, np.int64)])
        order = np.argsort(merged, kind="stable")
        order_j = jnp.asarray(order)
        self.chunk_ids = merged[order]
        self.params = jnp.concatenate([self.params, p_rows])[order_j]
        self.state = tuple(
            jnp.concatenate([s, r])[order_j] for s, r in zip(self.state, s_rows)
        )


# ---------------------------------------------------------------------------
# fabric
# ---------------------------------------------------------------------------
class PBoxFabric:
    """Chunk-sharded PS fabric over N aggregation engines.

    Synchronization modes (identical admission semantics to the old
    single-engine PHubServer; tested for back-compat in tests/test_server.py):

      sync      barrier every step (BSP; the paper's setting)
      async     each completed push is applied immediately, chunk-routed to
                the owning shards (Hogwild-PS)
      stale(s)  bounded staleness: a worker may run at most ``s`` steps ahead
                of the slowest worker (SSP); s=0 == sync

    Workers may push the whole flat gradient at once (``push``) or
    chunk-group by chunk-group (``push_chunks``); a push completes — and
    enters admission — once every chunk of the flat space has been staged.

    Every push carries the params version (fabric step) the worker last
    pulled: in sync mode a push computed against a version the rounds have
    already superseded (backup-quorum fired without it) is dropped
    (``ServerStats.late_pushes_dropped``) — stale gradients never count
    toward, or contaminate, a later round's quorum, and a straggler that
    re-pulls current params loses only the superseded gradient, never its
    fresh ones.  SSP mode admits late pushes instead (bounded staleness
    hides slowness *without* losing gradients); async applies every push
    immediately.

    Attach a ``NetworkTopology`` (+ optional ``CompressionConfig``) to
    model the rack tier: worker pushes cross the codec'd rack link to their
    ToR, are combined there, and one stream per rack crosses the
    oversubscribed core link (see core/topology.py).  ToR combining only
    exists where rounds exist: in ``async`` mode every completed push is
    applied immediately, so there is nothing for the switch to batch — the
    codec'd stream still crosses both tiers, but each worker stream pays
    the core link individually (``rack_streams`` stays 0).
    """

    def __init__(
        self,
        space: ParamSpace,
        spec: OptimizerSpec,
        init_flat: jax.Array,
        *,
        config: FabricConfig | None = None,
        shared_clock: Any | None = None,
        **legacy: Any,
    ):
        # Primary surface: one validated FabricConfig (core/config.py).
        # The pre-consolidation keyword spread is still accepted through
        # the from_legacy_kwargs adapter, which warns once per call site
        # — scripts/check_deprecated.py keeps src/ and benchmarks/ off
        # that path (tests exercise it on purpose).  ``shared_clock``
        # stays a live constructor argument: it is a *runtime* link to
        # the owning MultiJobFabric, not a reproducible config value.
        if config is not None and legacy:
            raise TypeError(
                "pass config=FabricConfig(...) or legacy keywords, not "
                f"both (got legacy {sorted(legacy)})")
        if config is None:
            if legacy:
                warn_legacy_call()
            config = FabricConfig.from_legacy_kwargs(**legacy)
        # every cross-field rule fails HERE, before any state is built
        config.validate()
        self.config = config
        num_shards = config.num_shards
        mode = config.mode
        num_workers = config.num_workers
        use_pallas = config.use_pallas
        placement = config.placement.policy
        topology: NetworkTopology | None = config.wire.topology
        compression = config.wire.compression
        fused_wire_path = config.wire.fused_wire_path
        replication = config.faults.replication
        fault_plan: FaultPlan | None = config.faults.fault_plan
        plan = config.placement.plan
        self.space = space
        self.spec = spec
        self.mode = mode
        self.staleness = (
            config.staleness if mode == "stale"
            else (0 if mode == "sync" else 1 << 30)
        )
        self.num_workers = num_workers
        self.num_shards = num_shards
        self.min_push_fraction = config.min_push_fraction
        self.use_pallas = use_pallas
        self.link = config.wire.link or LinkModel()
        # placement layer (core/placement.py): every fabric runs under a
        # plan.  None means the default plan — provably bit-identical to
        # the pre-placement-layer heuristics (the default plan's chain
        # racks ARE topology.replica_racks' formula, its chunk ownership
        # defers to ``placement``'s policy), so the caller's topology
        # object is kept as-is (attached tiers may hold it by identity).
        # An explicit plan is attached via ``with_plan`` so placement
        # queries read the plan's decisions instead of the formula.
        self.placement_policy = placement
        explicit_plan = plan is not None
        n_racks = topology.num_racks if topology is not None else 1
        if plan is None:
            plan = PlacementPlan.default(num_shards, num_racks=n_racks,
                                         replication=replication)
        self._check_plan(plan, num_shards, n_racks, replication)
        self.plan = plan
        self.topology = (topology.with_plan(plan)
                         if topology is not None and explicit_plan
                         else topology)
        # multi-tenant hooks (core/tenancy.py): ``namespace``/``chunk_base``
        # place this fabric's chunk space inside a fabric-wide namespace
        # (global chunk id = chunk_base + local id); ``shared_clock`` lets a
        # MultiJobFabric inflate this job's wire stages for co-tenant
        # contention.  Both only affect routing metadata and the event
        # clock — numerics stay those of a dedicated fabric by construction.
        self.namespace = config.namespace
        self.chunk_base = config.chunk_base
        self.shared_clock = shared_clock
        # codec chunks align with PS chunks so per-chunk scales ride the
        # same wire framing
        self.compression = dataclasses.replace(
            compression or CompressionConfig(codec="none"),
            chunk_elems=space.chunk_elems,
        )
        # fused wire path (kernels/wire_path): ship codec'd pushes to the
        # shards still encoded and let the single-pass kernel decode +
        # aggregate + optimize in VMEM.  The knob is advisory — the
        # effective flag also requires the Pallas tier and a codec x
        # optimizer x chunk-geometry combination the kernel supports
        # (wire_path_supported); anything else falls back to the unfused
        # decode-then-apply pipeline.  Codec "none" always takes the
        # legacy path: a raw f32 stream has no decode stage to fuse (it
        # already runs single-pass through kernels/fused_agg_opt).
        self.fused_wire_path = bool(fused_wire_path)
        self._fused_wire = (
            self.fused_wire_path
            and use_pallas
            and wire_path_supported(self.compression.codec, spec,
                                    space.chunk_elems)
        )
        # in-network switch tier (core/topology.SwitchCompute): each ToR
        # optionally owns a bounded pool of aggregation slots; a core-link
        # pool combines the rack uplinks.  Offload is full-slab-or-nothing
        # (a pool takes a round iff it is alive and holds >= num_chunks
        # slots), so exhaustion/failure fallback is the bit-exact software
        # combine, and codec "none" never engages (the switch does integer
        # arithmetic over the int8 wire format only).
        sw = config.wire.switch
        self.switch_cfg = sw
        self.rack_aggs: list[RackAggregator] = []
        if topology is not None:
            self.rack_aggs = [
                RackAggregator(
                    r, topology.members(r), self.compression,
                    space.flat_elems,
                    switch=(SwitchCompute(f"tor{r}", sw.tor_slots)
                            if sw.enabled else None),
                )
                for r in range(topology.num_racks)
            ]
        self.core_switch = (
            SwitchCompute("core", sw.core_slots)
            if sw.enabled and sw.core_slots > 0 and topology is not None
            else None
        )
        self._core_ef = (init_ef_state(self.compression, space.flat_elems)
                         if self.core_switch is not None else None)
        self._switch_cursor = 0  # fault_plan rounds consumed mid-round
        self._deferred: set[int] = set()  # raw pushes parked for the switch
        self._round_switch_chunks = 0  # pool occupancy of the last round
        # without a topology the codec still runs on the worker -> PS wire
        # (byte savings are never reported without their quantization cost);
        # the per-worker NIC error-feedback state lives here instead of at
        # a ToR
        self._worker_ef: dict[int, Any] = {}
        if topology is None and self.compression.codec != "none":
            self._worker_ef = {
                w: init_ef_state(self.compression, space.flat_elems)
                for w in range(num_workers)
            }
        self.step = 0
        self.worker_clock = np.zeros(num_workers, dtype=np.int64)
        # params version (fabric step) each worker last pulled: the version
        # its in-flight gradient was computed against — what sync-mode
        # admission judges freshness by
        self._pull_step = np.zeros(num_workers, dtype=np.int64)
        self._drops_since_step = 0  # guards against silent all-stale halt
        self.stats = ServerStats()

        c = space.num_chunks
        rows = init_flat.astype(jnp.float32).reshape(c, space.chunk_elems)
        self.chunk_owner = np.empty(c, dtype=np.int64)
        self.shards: list[PBoxShard] = []
        if plan.chunk_owner is not None:
            # the plan pins chunk ownership explicitly (a solved or
            # snapshot plan); the policy string is ignored
            if len(plan.chunk_owner) != c:
                raise ValueError(
                    f"plan places {len(plan.chunk_owner)} chunks, the "
                    f"space has {c}")
            assignment = [np.flatnonzero(plan.chunk_owner == s)
                          for s in range(num_shards)]
        elif placement == "round_robin":
            # the paper's core assignment: chunk c -> engine c % N, so a
            # streamed push feeds every engine continuously
            assignment = [np.arange(c)[np.arange(c) % num_shards == s]
                          for s in range(num_shards)]
        else:
            assignment = np.array_split(np.arange(c), num_shards)
        for sid, ids in enumerate(assignment):
            self.chunk_owner[ids] = sid
            self.shards.append(
                PBoxShard(sid, space, spec, ids, rows[jnp.asarray(ids)],
                          use_pallas=use_pallas)
            )
        # sync/stale inbox: worker -> (num_chunks, chunk_elems) gradient rows
        self._inbox: dict[int, jax.Array] = {}
        # chunk-by-chunk staging: worker -> (host rows buffer, staged mask)
        self._staged: dict[int, tuple] = {}
        self._flat_cache: jax.Array | None = None
        # fault-tolerance tier (core/replication.py): chain replication at
        # factor R, a deterministic fault schedule fired at round edges,
        # and the crash bookkeeping failover routing reads
        self.replication = replication
        self.fault_plan = fault_plan
        self.fault_trace: list[dict] = []
        self.dead_workers: set[int] = set()
        self._link_degrade: dict[int, float] = {}  # rack -> slowdown >= 1
        self._fault_cursor = 0  # last round whose faults already fired
        # read plane (core/serving.py): attached ReadPlanes register here
        # (as weakrefs — a dropped plane's caches must stay collectable)
        # so restore() can invalidate their version-stamped caches.  The
        # serving tier never writes fabric state — attaching a plane
        # leaves training bit-identical by construction.
        self.read_planes: list[Any] = []  # list[weakref.ref[ReadPlane]]
        # sparse tier (core/sparse.py): attached SparseTiers register here
        # (weakrefs, same collectability argument) so crash_shard can fail
        # their co-resident row slices over with the dense slab and
        # restore() can invalidate their serving caches.
        self.sparse_tiers: list[Any] = []  # list[weakref.ref[SparseTier]]
        self.replicas: list[ReplicaGroup] = []
        if replication > 1:
            # chain racks come from the plan (the default plan reproduces
            # topology.replica_racks' anti-affine formula exactly; with no
            # topology the plan is single-rack and everything is local)
            racks = plan.replica_racks[:, :replication]
            self.replicas = [
                ReplicaGroup(s.shard_id, replication, racks[s.shard_id])
                for s in self.shards
            ]
            # initial provisioning copies are free: they ship with the
            # model broadcast, not on the training wire
            for group, shard in zip(self.replicas, self.shards):
                group.sync(shard, round_=0)

    @staticmethod
    def _check_plan(plan: PlacementPlan, num_shards: int, num_racks: int,
                    replication: int) -> None:
        if plan.num_shards != num_shards:
            raise ValueError(
                f"plan places {plan.num_shards} shards, fabric has "
                f"{num_shards}")
        if plan.num_racks != num_racks:
            raise ValueError(
                f"plan places {plan.num_racks} racks, topology has "
                f"{num_racks}")
        if plan.replica_racks.shape[1] < replication:
            raise ValueError(
                f"plan places {plan.replica_racks.shape[1]} chain copies, "
                f"fabric replicates at {replication}")

    # -- assembled views -----------------------------------------------
    def _assemble_rows(self, per_shard: Callable[[PBoxShard], Any]) -> jax.Array:
        rows = jnp.zeros((self.space.num_chunks, self.space.chunk_elems),
                         jnp.float32)
        for shard in self.shards:
            if shard.num_chunks:
                rows = rows.at[jnp.asarray(shard.chunk_ids)].set(per_shard(shard))
        return rows

    @property
    def params(self) -> jax.Array:
        """The full flat parameter space, assembled from the shards."""
        if self._flat_cache is None:
            self._flat_cache = self._assemble_rows(
                lambda s: s.params).reshape(-1)
        return self._flat_cache

    # -- liveness / quorum ---------------------------------------------
    @property
    def num_alive_workers(self) -> int:
        return self.num_workers - len(self.dead_workers)

    @property
    def min_pushes(self) -> int:
        """Quorum size over the *alive* worker population: a crashed
        worker shrinks the barrier (elastic semantics) instead of
        deadlocking every surviving worker's round."""
        return max(1, int(np.ceil(self.min_push_fraction
                                  * self.num_alive_workers)))

    def alive(self, worker: int) -> bool:
        return worker not in self.dead_workers

    # -- worker API ----------------------------------------------------
    def pull(self, worker: int) -> jax.Array:
        flat = self.params
        self._pull_step[worker] = self.step
        self.stats.pulls += 1
        self.stats.bytes_pulled += flat.size * 4
        self.stats.chunk_pulls += self.space.num_chunks
        for shard in self.shards:
            shard.stats.chunk_pulls += shard.num_chunks
            shard.stats.bytes_pulled += shard.num_elems * 4
        return flat

    def can_proceed(self, worker: int) -> bool:
        """SSP admission: worker may start its next step iff it is within
        ``staleness`` steps of the slowest *alive* worker.  A crashed
        worker neither proceeds nor holds the staleness window hostage —
        its stalled clock is excluded until it re-enters."""
        if worker in self.dead_workers:
            return False
        clocks = self.worker_clock
        if self.dead_workers:
            alive = [c for w, c in enumerate(clocks)
                     if w not in self.dead_workers]
            return clocks[worker] - min(alive) <= self.staleness
        return clocks[worker] - clocks.min() <= self.staleness

    def push(self, worker: int, gflat: jax.Array) -> None:
        """Push the whole flat gradient in one call."""
        if gflat.shape != (self.space.flat_elems,):
            raise ValueError("bad gradient shape")
        self._complete_push(
            worker, gflat.reshape(self.space.num_chunks, self.space.chunk_elems)
        )

    def push_chunks(
        self, worker: int, chunk_ids: Sequence[int] | np.ndarray,
        gchunks: jax.Array,
    ) -> None:
        """Stage a worker's gradient for a subset of chunks.

        ``gchunks``: (len(chunk_ids), chunk_elems).  The push completes (and
        enters sync/async/SSP admission) once all chunks are staged."""
        ids = np.asarray(chunk_ids, dtype=np.int64)
        if gchunks.shape != (len(ids), self.space.chunk_elems):
            raise ValueError("bad chunk gradient shape")
        if worker not in self._staged:
            # host-side staging buffer, mutated in place — streaming a push
            # in G groups costs one device->host copy per group plus a
            # single host->device copy at completion, not G full-buffer
            # functional updates
            self._staged[worker] = (
                np.zeros((self.space.num_chunks, self.space.chunk_elems),
                         np.float32),
                np.zeros(self.space.num_chunks, dtype=bool),
            )
        buf, mask = self._staged[worker]
        buf[ids] = np.asarray(gchunks, np.float32)
        mask[ids] = True
        if mask.all():
            self._staged.pop(worker)
            self._complete_push(worker, jnp.asarray(buf))

    # -- push completion / admission ------------------------------------
    def _rack_agg_on(self) -> bool:
        # async has no rounds, so the ToR has nothing to batch (see class
        # docstring) — rack aggregation is a sync/SSP round concept
        return (self.topology is not None and self.topology.rack_aggregation
                and self.mode != "async")

    def _switch_on(self) -> bool:
        # the switch tier rides the rack tier and speaks only the int8
        # wire format (integer slot arithmetic) — codec "none"/bf16 keep
        # the software path, which is what the codec-"none" bit-identity
        # invariant hangs on
        return (self._rack_agg_on() and self.switch_cfg.enabled
                and self.compression.codec == "int8")

    def _complete_push(self, worker: int, gchunks: jax.Array) -> None:
        if worker in self.dead_workers:
            raise RuntimeError(
                f"worker {worker} crashed at round {self.step} and has not "
                "re-entered; revive it (runtime/elastic.worker_reentry) "
                "before pushing"
            )
        self.worker_clock[worker] += 1
        nbytes = wire_bytes(self.compression, gchunks.size)
        self.stats.pushes += 1
        self.stats.bytes_pushed += nbytes
        self.stats.chunk_pushes += self.space.num_chunks
        if self.topology is not None:
            self.stats.bytes_rack_link += nbytes
        # Backup-quorum semantics: a gradient computed against a params
        # version older than the current one belongs to a round that
        # already aggregated without it — drop it at admission (it is not
        # fresh for the current round, and counting it toward the next
        # quorum would both bias the update and let leftover stragglers
        # alone trigger a round).  Freshness is the fabric step at the
        # worker's last *pull* — a straggler that re-pulls and recomputes
        # loses only the one superseded gradient, never its fresh ones.
        # Only quorum rounds can supersede a worker's gradient, so the
        # rule applies exactly when the quorum is a strict subset of the
        # alive workers (see _barrier_met): full-barrier sync — including
        # ceil(fraction * alive) == alive — waits for everyone (dropping
        # there would deadlock push-only callers), SSP *admits* late
        # gradients by design
        # (runtime/straggler.py), and async has no rounds at all.
        if (self.mode == "sync" and self.min_pushes < self.num_alive_workers
                and int(self._pull_step[worker]) < self.step):
            self.stats.late_pushes_dropped += 1
            self._drops_since_step += 1
            if self.topology is not None:
                # the stale stream spent the rack link either way
                self.rack_aggs[self.topology.rack_of[worker]].drop_stale()
            if not self._rack_agg_on():
                # no aggregating ToR to refuse it early: the stream crossed
                # the core before the PS could drop it
                self.stats.bytes_core_link += nbytes
            if (self._drops_since_step >= self.num_workers
                    and bool((self._pull_step < self.step).all())):
                # every worker is pushing superseded gradients and nobody
                # has re-pulled: the driver forgot the pull step and no
                # round could ever fire again — fail loudly instead of
                # silently dropping forever
                raise RuntimeError(
                    "all workers' pushes were computed against params "
                    f"superseded by round {self.step}; pull between rounds "
                    "so gradients are fresh (see PBoxFabric docstring)"
                )
            return
        if not self._rack_agg_on():
            # no ToR combining: the worker's stream crosses the core itself
            # and reaches the shards directly (with ToR aggregation, both
            # are charged per combined stream in _rack_aggregate instead)
            self.stats.bytes_core_link += nbytes
            for shard in self.shards:
                shard.stats.chunk_pushes += shard.num_chunks
                shard.stats.bytes_pushed += wire_bytes(self.compression,
                                                       shard.num_elems)
        # Wire crossing to the PS.  With the fused wire path on and no
        # aggregating ToR in between, the worker's stream stays *encoded*
        # (WirePayload) all the way to the shards — the single-pass kernel
        # decodes it in VMEM.  With ToR aggregation the switch must decode
        # to combine, so the edge hop keeps the legacy round-trip and the
        # wire-direct hop moves to the rack uplink (_rack_aggregate).
        wire: WirePayload | None = None
        if self.topology is not None:
            rack = self.rack_aggs[self.topology.rack_of[worker]]
            if (self._switch_on() and rack.switch is not None
                    and rack.switch.alive
                    and rack.switch.slots >= self.space.num_chunks):
                # switch-pool candidate: park the slab RAW (the pool's
                # shared group scale needs every member's magnitude, so
                # quantization waits for _rack_aggregate) and book the
                # rack-link crossing now.  Full-slab-or-nothing: a pool
                # that cannot hold every chunk never engages, so the
                # fallback is the bit-exact software combine.  The final
                # offload decision (can_offload) happens at the round
                # edge — a switch_fail consumed mid-round between this
                # push and aggregation flips the whole rack to fallback.
                rack.ingest_deferred(worker)
                self._deferred.add(worker)
            elif self._fused_wire and not self._rack_agg_on():
                wire = rack.ingest_wire(worker, gchunks.reshape(-1))
            else:
                dec = rack.ingest(worker, gchunks.reshape(-1))
                gchunks = dec.reshape(self.space.num_chunks,
                                      self.space.chunk_elems)
        elif self.compression.codec != "none":
            if self._fused_wire:
                wire, self._worker_ef[worker] = encode_wire(
                    self.compression, gchunks.reshape(-1),
                    self._worker_ef[worker])
            else:
                dec, self._worker_ef[worker] = roundtrip(
                    self.compression, gchunks.reshape(-1),
                    self._worker_ef[worker])
                gchunks = dec.reshape(self.space.num_chunks,
                                      self.space.chunk_elems)
        if self.mode == "async":
            self.step += 1
            if wire is not None:
                pay = wire.payload.reshape(self.space.num_chunks,
                                           self.space.chunk_elems)
                for shard in self.shards:
                    if shard.num_chunks:
                        ids = jnp.asarray(shard.chunk_ids)
                        shard.apply_wire(
                            pay[ids][None],
                            None if wire.scale is None
                            else wire.scale[ids][None],
                            wire.codec, self.step, average=False)
                self.stats.fused_wire_rounds += 1
            else:
                for shard in self.shards:
                    if shard.num_chunks:
                        shard.apply(
                            gchunks[jnp.asarray(shard.chunk_ids)][None],
                            self.step, average=False)
            self.stats.steps += 1
            self._simulate_round(streams=1 if self.topology else None)
            self._flat_cache = None
            self._replicate_round()
            self._fire_faults()
            return
        self._inbox[worker] = gchunks if wire is None else wire
        if len(self._inbox) >= self.min_pushes and self._barrier_met():
            self._aggregate()

    def _barrier_met(self) -> bool:
        # quorum mode exists only when the quorum is a *strict* subset of
        # the alive population: ceil(fraction * alive) == alive is a full
        # barrier regardless of the fraction (dropping there would let a
        # push-only caller deadlock — the round needs everyone anyway)
        if self.min_pushes < self.num_alive_workers:
            # backup-worker mode: quorum reached (the inbox only ever holds
            # current-round pushes — stale ones were dropped at admission)
            return True
        # full barrier: every *alive* worker (a crashed worker's missing
        # push must not deadlock the survivors' round)
        return len(self._inbox) == self.num_alive_workers

    def _aggregate(self) -> None:
        workers = sorted(self._inbox)
        if len(workers) < self.num_workers:
            self.stats.partial_aggregations += 1
        self.step += 1
        streams = None
        if self._rack_agg_on():
            streams = self._rack_aggregate(workers)
        else:
            if self.topology is not None:
                streams = len(workers)  # every worker stream crosses the core
            if self._fused_wire:
                # inbox holds WirePayloads: stack the encoded streams per
                # shard and let the single-pass kernel decode in VMEM
                codec = self.compression.codec
                shape = (self.space.num_chunks, self.space.chunk_elems)
                pays = [self._inbox[w] for w in workers]
                for shard in self.shards:
                    if not shard.num_chunks:
                        continue
                    ids = jnp.asarray(shard.chunk_ids)
                    pay = jnp.stack(
                        [wp.payload.reshape(shape)[ids] for wp in pays])
                    sc = (jnp.stack([wp.scale[ids] for wp in pays])
                          if codec == "int8" else None)
                    shard.apply_wire(pay, sc, codec, self.step, average=True)
                self.stats.fused_wire_rounds += 1
            else:
                for shard in self.shards:
                    if not shard.num_chunks:
                        continue
                    ids = jnp.asarray(shard.chunk_ids)
                    grads = jnp.stack([self._inbox[w][ids] for w in workers])
                    shard.apply(grads, self.step, average=True)
        self._inbox.clear()
        self._deferred.clear()
        self.stats.steps += 1
        self._drops_since_step = 0
        self._simulate_round(streams=streams)
        self._flat_cache = None
        # chain replication completes before the round edge: a crash
        # scheduled at this round promotes the post-round bits
        self._replicate_round()
        self._fire_faults()

    def _rack_aggregate(self, workers: list[int]) -> int:
        """Combine this round's pushes rack by rack, then apply the
        upstream stream(s) to every shard.  Returns the number of streams
        that crossed the core link.

        f32 (codec "none") chains the running partial through the racks in
        ascending worker order — the exact add sequence of the fused
        kernel's left fold, so it is bit-identical to the flat fabric for
        any contiguous layout and any quorum subset.  Integer codecs are
        associative on the wire (the paper's argument for integer switch
        math): each rack combines independently, re-encodes at the ToR,
        and the PBox folds the decoded rack streams in rack order.

        The streams are applied through the *same* (K, n) kernel program
        the flat fabric uses — zero rows stand in for the per-worker
        streams the ToRs absorbed (x + 0 is exact, and the shared program
        shape keeps XLA's fusion/FMA choices identical, which makes the
        bit-equality structural rather than incidental).  The averaging
        divisor is the worker count either way."""
        # switch faults land mid-round: a pool scheduled to fail at this
        # round must refuse THIS round's offload (the fallback edge the
        # bit-identity invariant tests), not next round's
        self._consume_switch_faults()
        self._round_switch_chunks = 0
        streams: list[jax.Array] = []
        wire_streams: list[WirePayload] = []
        shipped = 0
        present = set(workers)
        c = self.space.num_chunks
        active = [(rack, [w for w in rack.members if w in present])
                  for rack in self.rack_aggs]
        active = [(rack, members) for rack, members in active if members]
        # core pool: engages only when >= 2 rack streams would cross the
        # core link (a single stream has nothing to combine with) and the
        # fused wire path can carry the pool's re-encoded egress
        use_core = (
            self._switch_on() and self.core_switch is not None
            and self._fused_wire and len(active) >= 2
            and self.core_switch.can_offload(c)
        )
        core_racks: list[RackAggregator] = []
        core_slabs: list[jax.Array] = []
        offloaded = fallback = False
        carry = None  # codec "none": running prefix chained through racks
        for rack, members in active:
            if self.compression.codec == "none":
                for w in members:
                    g = self._inbox[w]
                    carry = g if carry is None else carry + g
                relay = rack.uplink(carry.reshape(-1)).reshape(carry.shape)
                streams = [relay]  # the chain's latest prefix supersedes
            else:
                if any(w in self._deferred for w in members):
                    # the rack's pushes were parked raw for the pool;
                    # can_offload is the round-edge decision — a pool that
                    # failed since push time flips the whole rack to the
                    # bit-exact software combine
                    pushes = [(w, self._inbox[w].reshape(-1))
                              for w in members]
                    if rack.switch.can_offload(c):
                        local = rack.switch_combine(pushes)
                        self._round_switch_chunks += c
                        self.stats.bytes_switch_agg += (
                            (self.space.flat_elems + 4 * c) * len(pushes))
                        offloaded = True
                    else:
                        local = rack.software_combine(pushes)
                        fallback = True
                    local = local.reshape(c, self.space.chunk_elems)
                else:
                    local = None
                    for w in members:
                        g = self._inbox[w]
                        local = g if local is None else local + g
                if use_core:
                    # stage for the core pool — quantization is coordinated
                    # across racks below (shared group scale)
                    core_racks.append(rack)
                    core_slabs.append(rack.uplink_pool(local.reshape(-1)))
                elif self._fused_wire:
                    # fused wire path: the re-encoded rack stream crosses
                    # the core *still encoded*; the shards' single-pass
                    # kernel decodes it in VMEM (same switch EF + bytes)
                    wire_streams.append(rack.uplink_wire(local.reshape(-1)))
                else:
                    streams.append(
                        rack.uplink(local.reshape(-1)).reshape(local.shape))
            shipped += 1
            self.stats.bytes_core_link += wire_bytes(self.compression,
                                                     self.space.flat_elems)
            self.stats.rack_streams += 1
            if use_core:
                continue  # single PS-ingress stream, charged at pool egress
            # shard ingress: one combined stream per rack reaches the PS
            for shard in self.shards:
                shard.stats.chunk_pushes += shard.num_chunks
                shard.stats.bytes_pushed += wire_bytes(self.compression,
                                                       shard.num_elems)
        if offloaded:
            self.stats.switch_rounds += 1
        if fallback:
            self.stats.switch_fallback_rounds += 1
        if use_core:
            # Core-pool crossing: the racks negotiate ONE shared per-chunk
            # scale (group_scale — max magnitude across rack slabs), each
            # ships int8 under it, and the pool's slot registers sum with
            # exact int32 adds.  The pool egress re-encodes once with the
            # core switch's own error feedback, so a single stream lands
            # at the PS ingress no matter how many racks fed the pool —
            # that absorbed landing is the tier's bandwidth win
            # (bytes_switch_saved); each rack stream still pays its own
            # core-link segment up to the switch (bytes_core_link above).
            e = self.space.chunk_elems
            s_sh = group_scale(core_slabs, e)
            s_elems = jnp.repeat(s_sh, e)
            qs = []
            for rack, slab2 in zip(core_racks, core_slabs):
                q = integer_quantize(slab2, s_sh, e)
                rack.commit_uplink(slab2, q, s_elems)
                qs.append(q)
            acc = self.core_switch.accumulate(qs, e)
            self._round_switch_chunks += c
            dec = acc.astype(jnp.float32) * s_elems
            slab_c = dec + self._core_ef if self._core_ef is not None else dec
            s_c = group_scale([slab_c], e)
            q_c = integer_quantize(slab_c, s_c, e)
            if self._core_ef is not None:
                self._core_ef = (
                    slab_c - q_c.astype(jnp.float32) * jnp.repeat(s_c, e))
            wire_streams.append(WirePayload("int8", q_c, s_c))
            self.stats.core_switch_rounds += 1
            self.stats.bytes_switch_agg += (
                (self.space.flat_elems + 4 * c) * len(qs))
            self.stats.bytes_switch_saved += (
                (len(core_racks) - 1)
                * wire_bytes(self.compression, self.space.flat_elems))
            for shard in self.shards:
                shard.stats.chunk_pushes += shard.num_chunks
                shard.stats.bytes_pushed += wire_bytes(self.compression,
                                                       shard.num_elems)
        if wire_streams:
            # zero rows stand in for the worker streams the ToRs absorbed,
            # exactly like the unfused branch below — a zero payload
            # decodes to exact 0.0 (int8: q=0 times any scale; bf16: zero
            # bits widen to +0.0f), so the fold adds the same zeros in the
            # same positions
            codec = self.compression.codec
            shape = (self.space.num_chunks, self.space.chunk_elems)
            n_zero = len(workers) - len(wire_streams)
            pay_rows = [wp.payload.reshape(shape) for wp in wire_streams]
            pay_rows += [jnp.zeros(shape, pay_rows[0].dtype)] * n_zero
            scale_rows = None
            if codec == "int8":
                scale_rows = [wp.scale for wp in wire_streams]
                scale_rows += [jnp.ones((self.space.num_chunks,),
                                        jnp.float32)] * n_zero
            for shard in self.shards:
                if not shard.num_chunks:
                    continue
                ids = jnp.asarray(shard.chunk_ids)
                pay = jnp.stack([r[ids] for r in pay_rows])
                sc = (None if scale_rows is None
                      else jnp.stack([s[ids] for s in scale_rows]))
                shard.apply_wire(pay, sc, codec, self.step, average=True)
            self.stats.fused_wire_rounds += 1
            return shipped
        zero = jnp.zeros((self.space.num_chunks, self.space.chunk_elems),
                         jnp.float32)
        rows = streams + [zero] * (len(workers) - len(streams))
        for shard in self.shards:
            if not shard.num_chunks:
                continue
            ids = jnp.asarray(shard.chunk_ids)
            shard.apply(jnp.stack([r[ids] for r in rows]), self.step,
                        average=True)
        return shipped

    # -- event-ordered pipeline clock ------------------------------------
    def _simulate_round(self, streams: int | None = None) -> None:
        """Replay one aggregation round on the event clock: chunk c arrives
        at (c+1)*wire_us; each shard aggregates its chunks in arrival order,
        overlapping wire and engine time (chunk i aggregates while chunk i+1
        is in flight).

        With a topology, the wire becomes a two-stage pipeline: the rack
        link (codec-scaled ``wire_us_per_chunk``) feeds the ToR, then the
        oversubscribed core link relays each chunk onward (``streams``
        concurrent streams share a rack's uplink — 1 with ToR aggregation,
        the rack population without).

        With a ``shared_clock`` attached (multi-tenant fabric), both wire
        stages are inflated by the clock's fair-share scales before the
        replay, and the round's link occupancy is reported back so the
        shared per-link queues stay in sync."""
        rack_scale = core_scale = 1.0
        if self.shared_clock is not None:
            rack_scale, core_scale = self.shared_clock.wire_scales(self)
            if rack_scale < 1.0 or core_scale < 1.0:
                raise ValueError(
                    "shared-clock scales cannot beat a dedicated link")
        bpe_scale = wire_bytes(self.compression, self.space.chunk_elems) / (
            4.0 * self.space.chunk_elems
        )
        # fault tier: a degraded rack link slows the round's rack stage.
        # The clock is round-granular (one wire rate per stage), so the
        # worst active degradation gates the pipeline — the slowest rack
        # is the barrier in a sync round anyway.  Timing only, never bits.
        degrade = max(self._link_degrade.values(), default=1.0)
        wire = self.link.wire_us_per_chunk * bpe_scale * rack_scale * degrade
        agg = self.link.agg_us_per_chunk
        c = self.space.num_chunks
        idx = np.arange(c, dtype=np.float64)
        core = 0.0
        if self.topology is not None:
            share = (1.0 if streams is None
                     else max(1.0, streams / self.topology.num_racks))
            # rack_scale already rode in on ``wire``; apply only the extra
            # core-tier contention on top
            core = (wire * self.topology.oversubscription * share
                    * (core_scale / rack_scale))
            edge_done = (idx + 1.0) * wire
            # two-stage pipeline: the core relays chunk i while chunk i+1
            # still crosses the rack link
            arrival = (np.maximum.accumulate(edge_done - idx * core)
                       + (idx + 1.0) * core)
            self.stats.sim_core_wire_us += c * core
        else:
            arrival = (idx + 1.0) * wire
        makespan = 0.0
        for shard in self.shards:
            if not shard.num_chunks:
                continue
            arr = arrival[shard.chunk_ids]
            n = len(arr)
            # completion_i = max_{j<=i}(arrival_j - j*agg) + (i+1)*agg
            shifted = arr - np.arange(n) * agg
            done = np.maximum.accumulate(shifted) + (np.arange(n) + 1) * agg
            makespan = max(makespan, float(done[-1]))
            shard.stats.sim_busy_us += n * agg
        self.stats.sim_wire_us += c * wire
        self.stats.sim_agg_us += c * agg
        self.stats.sim_pipelined_us += makespan
        self.stats.sim_serialized_us += c * wire + c * core + c * agg
        if self.shared_clock is not None:
            self.shared_clock.record_round(
                self,
                rack_us=c * wire,
                core_us=c * core,
                rack_demand_us=c * wire / rack_scale,
                core_demand_us=c * core / core_scale,
                makespan_us=makespan,
            )
            # switch-pool occupancy joins the box's weighted-fair link
            # accounting.  Optional protocol method (hasattr-guarded, not
            # a record_round parameter) so existing clock shims — test
            # mocks included — keep working unmodified.
            if (self._round_switch_chunks
                    and hasattr(self.shared_clock, "record_switch")):
                self.shared_clock.record_switch(
                    self, pool_us=self._round_switch_chunks * agg)

    # -- fault tier: chain replication / failover / injection -------------
    def _hop_cost(self, src_rack: int, dst_rack: int) -> float:
        """Event-clock cost multiplier of one replication hop: rack-local
        hops ride the full-bisection tier, cross-rack hops pay the
        oversubscribed core (core/topology.py)."""
        if self.topology is None:
            return 1.0
        return self.topology.hop_cost(src_rack, dst_rack)

    def _account_state_stream(
        self, group: ReplicaGroup, shard: PBoxShard, *, resilver: bool
    ) -> None:
        """Book one chain pass (or one re-silver stream) for ``shard``:
        raw-f32 state bytes land on the same rack/core link accounting
        training traffic uses, and the event clock records the pass in
        ``sim_replication_us`` (chain replication overlaps the next round
        — it bounds failover lag, not the round makespan) or
        ``sim_recovery_us`` (re-silvering is the failover's cost)."""
        nbytes = group.state_bytes(self.spec.num_state_slots,
                                   shard.num_elems)
        hops = group.hop_racks()
        if resilver:
            # one stream from the surviving chain onto the replacement
            hops = hops[:1]
        us_per_chunk = self.link.wire_us_per_chunk * (
            1 + self.spec.num_state_slots)
        for src, dst in hops:
            if resilver:
                self.stats.bytes_resilver += nbytes
            else:
                self.stats.bytes_replication += nbytes
            if self.topology is not None:
                if src == dst:
                    self.stats.bytes_rack_link += nbytes
                else:
                    self.stats.bytes_core_link += nbytes
            us = shard.num_chunks * us_per_chunk * self._hop_cost(src, dst)
            if resilver:
                self.stats.sim_recovery_us += us
            else:
                self.stats.sim_replication_us += us

    def _replicate_round(self) -> None:
        """One chain pass after a completed round: every backup now holds
        the primary's exact post-round slab (raw f32 — see
        ReplicaGroup.state_bytes), so a crash at this round edge fails
        over bit-exactly."""
        if not self.replicas:
            return
        for group, shard in zip(self.replicas, self.shards):
            if shard.num_chunks:
                self._account_state_stream(group, shard, resilver=False)
            group.sync(shard, round_=self.step)
        self.stats.replication_rounds += 1

    def _fire_faults(self) -> None:
        """Inject every scheduled fault whose round the event clock just
        passed.  Rounds are the only crash points — deterministic,
        replayable, and always after the round's chain replication.
        Switch faults are the one exception: they are consumed *mid*-round
        (``_consume_switch_faults``, own cursor) so a pool scheduled to
        fail at round r refuses round r's offload — here they only catch
        up on rounds that never reached ``_rack_aggregate``."""
        if self.fault_plan is None:
            return
        self._consume_switch_faults()
        due = self.fault_plan.between(self._fault_cursor, self.step)
        self._fault_cursor = self.step
        for ev in due:
            self._apply_fault(ev)

    def _consume_switch_faults(self) -> None:
        """Fire due ``switch_fail``/``switch_restore`` events.  Runs at
        the top of ``_rack_aggregate`` — BEFORE the round's offload
        decision — on a cursor separate from ``_fault_cursor`` (the other
        kinds still fire at the round edge, after replication).  Target
        rack id flips that ToR's pool; target == num_racks flips the core
        pool.  Without a switch tier the events are recorded as ignored —
        a plan stays replayable on any fabric."""
        if self.fault_plan is None:
            return
        due = self.fault_plan.between(self._switch_cursor, self.step)
        self._switch_cursor = self.step
        n_racks = len(self.rack_aggs)
        for ev in due:
            if ev.kind not in ("switch_fail", "switch_restore"):
                continue
            rec: dict[str, Any] = {"round": int(self.step),
                                   "event": ev.to_json()}
            if not 0 <= ev.target <= n_racks:
                raise ValueError(
                    f"{ev.kind} targets switch {ev.target}; the fabric has "
                    f"{n_racks} ToR pools + 1 core pool")
            sw = (self.core_switch if ev.target == n_racks
                  else self.rack_aggs[ev.target].switch
                  if self.rack_aggs else None)
            if sw is None:
                rec["action"] = "ignored_no_switch_tier"
            elif ev.kind == "switch_fail":
                sw.fail()
                self.stats.switch_failures += 1
                rec["action"] = f"switch_failed:{sw.name}"
            else:
                sw.restore()
                self.stats.switch_restores += 1
                rec["action"] = f"switch_restored:{sw.name}"
            self.fault_trace.append(rec)

    def _apply_fault(self, ev) -> None:
        if ev.kind in ("switch_fail", "switch_restore"):
            return  # consumed mid-round by _consume_switch_faults
        rec: dict[str, Any] = {"round": int(self.step), "event": ev.to_json()}
        if ev.kind == "shard_crash":
            self.fault_trace.append(rec)  # record before a possible raise
            rec["action"] = self.crash_shard(ev.target)
        elif ev.kind == "worker_crash":
            self.crash_worker(ev.target)
            rec["action"] = "worker_crashed"
            self.fault_trace.append(rec)
        elif ev.kind == "worker_recover":
            # in-process recovery: the fabric state IS current, so revive
            # directly (same clock alignment as elastic.worker_reentry,
            # minus materializing a full snapshot just to discard it —
            # worker_reentry is for callers handing the snapshot to a
            # real replacement process)
            self.revive_worker(ev.target)
            rec["action"] = "worker_reentered"
            self.fault_trace.append(rec)
        elif ev.kind == "link_degrade":
            if self.topology is not None and not (
                    0 <= ev.target < self.topology.num_racks):
                raise ValueError(f"link_degrade targets rack {ev.target}, "
                                 "not in the topology")
            self._link_degrade[ev.target] = ev.factor
            self.stats.link_degrades += 1
            rec["action"] = f"link_degraded_x{ev.factor:g}"
            self.fault_trace.append(rec)
        elif ev.kind == "link_restore":
            self._link_degrade.pop(ev.target, None)
            rec["action"] = "link_restored"
            self.fault_trace.append(rec)

    def crash_shard(self, shard_id: int) -> str:
        """One aggregation engine dies at a round edge.

        With a surviving chain (replication >= 2): promote the chain head
        — a byte-exact copy of the post-round slab — into a replacement
        engine, re-target routing at it (``chunk_owner`` is unchanged;
        the shard slot is), and re-silver a fresh backup so the chain is
        back at full strength.  Pushes/pulls in later rounds hit the
        replacement transparently and bit-identically.

        With replication == 1 the slab is simply gone: raises
        ``ShardLost`` (diagnosable) instead of serving corrupt state."""
        if not 0 <= shard_id < self.num_shards:
            raise ValueError(f"no shard {shard_id}")
        shard = self.shards[shard_id]
        self.stats.shards_crashed += 1
        if self.replication < 2 or not self.replicas:
            raise ShardLost(shard_id, shard.num_chunks, self.step,
                            self.replication)
        group = self.replicas[shard_id]
        chunk_ids, params, state = group.promote()
        replacement = PBoxShard(shard_id, self.space, self.spec, chunk_ids,
                                params, use_pallas=self.use_pallas)
        replacement.state = tuple(state)
        self.shards[shard_id] = replacement
        self.stats.failovers += 1
        # recovery: one state stream re-silvers the chain's empty slot
        # from the promoted replica
        if replacement.num_chunks:
            self._account_state_stream(group, replacement, resilver=True)
        group.sync(replacement, round_=self.step)
        self.stats.resilvers += 1
        # co-resident sparse row slices fail over with the dense slab (a
        # real engine loss takes both); dead tiers are pruned as we notify
        self.sparse_tiers = [r for r in self.sparse_tiers
                             if r() is not None]
        for ref in self.sparse_tiers:
            tier = ref()
            if tier is not None:
                tier.failover(shard_id)
        self._flat_cache = None
        return "failed_over"

    def crash_worker(self, worker: int) -> None:
        """A worker process dies: its in-flight stream (staged chunks, an
        un-aggregated inbox entry) dies with it, and the admission barrier
        shrinks to the surviving population.  If its missing push was the
        only thing holding this round's barrier, the round fires now."""
        if not 0 <= worker < self.num_workers:
            raise ValueError(f"no worker {worker}")
        if worker in self.dead_workers:
            return
        self.dead_workers.add(worker)
        self.stats.workers_crashed += 1
        self._staged.pop(worker, None)
        self._deferred.discard(worker)  # a parked raw push dies in flight
        dropped = self._inbox.pop(worker, None)
        if dropped is not None:
            self.worker_clock[worker] -= 1  # that push never happened
        if (self.mode != "async" and self._inbox
                and len(self._inbox) >= self.min_pushes
                and self._barrier_met()):
            self._aggregate()

    def revive_worker(self, worker: int, *, clock: int | None = None) -> None:
        """Re-admit a crashed worker (see runtime/elastic.worker_reentry:
        re-entry restores from the fabric's current snapshot, so the
        worker resumes on the current params version — its clock aligns
        with the restored step and its first push is fresh)."""
        if worker not in self.dead_workers:
            return
        self.dead_workers.discard(worker)
        self.stats.workers_recovered += 1
        self.worker_clock[worker] = self.step if clock is None else clock
        self._pull_step[worker] = self.step

    def export_fault_trace(self) -> dict:
        """The replayable failure record: the (deterministic) plan plus
        every injected event and the action taken — byte-for-byte replay
        is plan + initial state (CI uploads this JSON on chaos failures).

        Counts are derived from the trace, not ``ServerStats``: stats are
        cumulative across the whole process (a restore + replay counts a
        re-fired failover twice there, exactly like replayed rounds bump
        ``steps`` twice), while the trace — truncated on restore — is the
        current timeline and always matches the plan."""
        kinds: dict[str, int] = {}
        actions: dict[str, int] = {}
        for rec in self.fault_trace:
            k = rec["event"]["kind"]
            kinds[k] = kinds.get(k, 0) + 1
            a = rec.get("action")
            if a is not None:
                actions[a] = actions.get(a, 0) + 1
        return {
            "schema": 1,
            "replication": self.replication,
            "plan": self.fault_plan.to_json() if self.fault_plan else None,
            "trace": list(self.fault_trace),
            "round": int(self.step),
            "stats": {
                "shards_crashed": kinds.get("shard_crash", 0),
                "failovers": actions.get("failed_over", 0),
                "resilvers": actions.get("failed_over", 0),
                "workers_crashed": kinds.get("worker_crash", 0),
                "workers_recovered": kinds.get("worker_recover", 0),
                "link_degrades": kinds.get("link_degrade", 0),
            },
        }

    # -- placement-plan hooks ---------------------------------------------
    def rebalance(self, slow_shards: Sequence[int]) -> int:
        """Move all chunks owned by ``slow_shards`` to healthy shards
        (balance-preserving) — the straggler heuristic expressed as a
        plan delta (core/placement.chunk_rebalance_delta) and applied
        through ``apply_plan_delta``.  Pure ownership transfer:
        parameters and optimizer state move with their chunks, so
        training numerics are unchanged.  Returns the number of chunks
        moved."""
        delta = chunk_rebalance_delta(self.chunk_owner, list(slow_shards),
                                      self.num_shards)
        if delta is None:
            return 0
        return self.apply_plan_delta(delta)

    def apply_plan_delta(self, delta: PlanDelta) -> int:
        """Apply one placement-plan delta to the live fabric; returns a
        progress count (chunks moved, chain copies re-homed, or chunks
        re-assigned by a reshard).  Numerics-neutral by construction:
        every kind moves ownership metadata and byte/time accounting,
        never parameter or optimizer bits.  Frontend and tenant-share
        deltas belong to the read plane (``ReadPlane.move_frontend``) and
        the tenancy box (``MultiJobFabric.apply_tenant_shares``)."""
        if delta.kind == "chunk_moves":
            return self._apply_chunk_moves(delta.moves)
        if delta.kind == "replica_racks":
            return self.replace_chain_racks(delta.shard, delta.racks)
        if delta.kind == "shard_count":
            return self.reshard(delta.new_shards)
        raise ValueError(
            f"delta kind {delta.kind!r} is not fabric-applied (frontend "
            "moves belong to the read plane, tenant shares to the "
            "MultiJobFabric)")

    def _apply_chunk_moves(self, moves: Sequence[tuple[int, int]]) -> int:
        new_owner = self.chunk_owner.copy()
        for chunk, owner in moves:
            if not 0 <= chunk < self.space.num_chunks:
                raise ValueError(f"no chunk {chunk}")
            if not 0 <= owner < self.num_shards:
                raise ValueError(f"no shard {owner}")
            new_owner[chunk] = owner
        moved = np.where(new_owner != self.chunk_owner)[0]
        if len(moved) == 0:
            return 0
        stash_p: dict[int, Any] = {}
        stash_s: dict[int, Any] = {}
        for shard in self.shards:
            ids = moved[self.chunk_owner[moved] == shard.shard_id]
            if len(ids) == 0:
                continue
            p_rows, s_rows = shard.release(ids)
            for j, cid in enumerate(ids):
                stash_p[int(cid)] = p_rows[j]
                stash_s[int(cid)] = tuple(s[j] for s in s_rows)
        for shard in self.shards:
            ids = moved[new_owner[moved] == shard.shard_id]
            if len(ids) == 0:
                continue
            p_rows = jnp.stack([stash_p[int(cid)] for cid in ids])
            s_rows = tuple(
                jnp.stack([stash_s[int(cid)][k] for cid in ids])
                for k in range(self.spec.num_state_slots)
            )
            shard.adopt(ids, p_rows, s_rows)
        self.chunk_owner = new_owner
        self.stats.rebalances += 1
        self.stats.chunks_moved += len(moved)
        # replica chains follow their shard's new chunk set (the move
        # itself rides the rebalance transfer, not the replication wire)
        for group, shard in zip(self.replicas, self.shards):
            group.sync(shard, round_=self.step)
        self._flat_cache = None
        return len(moved)

    def replace_chain_racks(self, shard_id: int,
                            new_racks: Sequence[int]) -> int:
        """Re-home one shard's replication chain onto ``new_racks``
        (primary's home first, then the backups, like
        ``ReplicaGroup.racks``).  Returns the number of copies that
        actually moved.

        Numerics-neutral: chain copies are references to immutable
        post-round slabs, so "moving" one is metadata plus one state
        stream on the wire (booked as recovery-class traffic —
        ``bytes_resilver``/``sim_recovery_us`` — on the links the move
        crosses).  The fabric's plan and plan-backed topology are
        refreshed so serving routes and ``home_racks`` consumers see the
        new chain immediately."""
        if not self.replicas:
            raise ValueError(
                "no replication chains to re-home (replication < 2)")
        if not 0 <= shard_id < self.num_shards:
            raise ValueError(f"no shard {shard_id}")
        group = self.replicas[shard_id]
        new = tuple(int(r) for r in new_racks)
        if len(new) != group.factor:
            raise ValueError(
                f"chain has {group.factor} copies, got {len(new)} racks")
        n_racks = self.topology.num_racks if self.topology is not None else 1
        for r in new:
            if not 0 <= r < n_racks:
                raise ValueError(f"rack {r} not in the topology")
        old = group.racks
        if new == old:
            return 0
        shard = self.shards[shard_id]
        group.racks = new
        rr = np.asarray(self.plan.replica_racks).copy()
        rr[shard_id, :len(new)] = new
        self.plan = self.plan.replace(replica_racks=rr)
        if self.topology is not None:
            self.topology = self.topology.with_plan(self.plan)
        moved = 0
        if shard.num_chunks:
            nbytes = group.state_bytes(self.spec.num_state_slots,
                                       shard.num_elems)
            us_per_chunk = self.link.wire_us_per_chunk * (
                1 + self.spec.num_state_slots)
            for src, dst in zip(old, new):
                if src == dst:
                    continue
                moved += 1
                # one state stream ships the copy from its old rack to
                # the new one, on the same accounting surface failover
                # re-silvering uses
                self.stats.bytes_resilver += nbytes
                if self.topology is not None:
                    self.stats.bytes_core_link += nbytes
                self.stats.sim_recovery_us += (
                    shard.num_chunks * us_per_chunk
                    * self._hop_cost(src, dst))
        else:
            moved = sum(1 for a, b in zip(old, new) if a != b)
        self.stats.replica_moves += moved
        return moved

    def reshard(self, new_num_shards: int, *,
                plan: PlacementPlan | None = None) -> int:
        """Change the live fabric's shard count in place — the
        autoscaler's grow/shrink lever.  Returns the number of chunks
        whose owner changed.

        A round-edge operation: in-flight pushes (inbox or staged) must
        have drained, because staged buffers and quorum state are
        per-round.  The parameter space itself is untouched — resharding
        re-partitions the *same* chunk set over a different number of
        aggregation engines, so worker push/pull shapes, codec
        error-feedback state, worker clocks, and pull versions all stay
        exactly as they were.  Bit-identity across the change is the
        fabric's standing sharding-independence invariant: every shard
        applies the same per-chunk kernel program, so the partition never
        touches numerics.  Replication chains are rebuilt at the new
        count from ``plan`` (default: the anti-affine default plan) with
        a provisioning sync — the copies ride the rescale transfer like
        rebalanced chunks do.  Attached sparse tiers re-shard with the
        dense engines (co-residency); read-plane caches stay valid (bits
        and versions are unchanged)."""
        if new_num_shards < 1:
            raise ValueError("num_shards must be >= 1")
        if self._inbox or self._staged:
            raise RuntimeError(
                "reshard is a round-edge operation: in-flight pushes must "
                "drain (or be dropped) before the engine set changes")
        if new_num_shards == self.num_shards and plan is None:
            return 0
        n_racks = self.topology.num_racks if self.topology is not None else 1
        if plan is None:
            plan = PlacementPlan.default(new_num_shards, num_racks=n_racks,
                                         replication=self.replication)
        self._check_plan(plan, new_num_shards, n_racks, self.replication)
        c = self.space.num_chunks
        rows = self._assemble_rows(lambda s: s.params)
        state_rows = [self._assemble_rows(lambda s, k=k: s.state[k])
                      for k in range(self.spec.num_state_slots)]
        if plan.chunk_owner is not None:
            if len(plan.chunk_owner) != c:
                raise ValueError(
                    f"plan places {len(plan.chunk_owner)} chunks, the "
                    f"space has {c}")
            owner = np.asarray(plan.chunk_owner, dtype=np.int64).copy()
        elif self.placement_policy == "round_robin":
            owner = np.arange(c, dtype=np.int64) % new_num_shards
        else:
            owner = np.empty(c, dtype=np.int64)
            for sid, ids in enumerate(np.array_split(np.arange(c),
                                                     new_num_shards)):
                owner[ids] = sid
        moved = int(np.sum(owner != self.chunk_owner))
        new_shards: list[PBoxShard] = []
        for sid in range(new_num_shards):
            ids = np.flatnonzero(owner == sid)
            shard = PBoxShard(sid, self.space, self.spec, ids,
                              rows[jnp.asarray(ids)],
                              use_pallas=self.use_pallas)
            shard.state = tuple(r[jnp.asarray(ids)] for r in state_rows)
            new_shards.append(shard)
        self.shards = new_shards
        self.chunk_owner = owner
        self.num_shards = new_num_shards
        self.plan = plan
        if self.topology is not None:
            self.topology = self.topology.with_plan(plan)
        self.replicas = []
        if self.replication > 1:
            racks = plan.replica_racks[:, :self.replication]
            self.replicas = [
                ReplicaGroup(s.shard_id, self.replication, racks[s.shard_id])
                for s in self.shards
            ]
            for group, shard in zip(self.replicas, self.shards):
                group.sync(shard, round_=self.step)
        self.stats.rescales += 1
        self.stats.chunks_moved += moved
        self._flat_cache = None
        # co-resident sparse tiers re-shard with the dense engines
        self.sparse_tiers = [r for r in self.sparse_tiers
                             if r() is not None]
        for ref in self.sparse_tiers:
            tier = ref()
            if tier is not None:
                tier.reshard(new_num_shards)
        return moved

    # -- elastic / checkpoint hooks ---------------------------------------
    def snapshot(self) -> dict:
        """Crash-consistent snapshot of the committed training state.

        Taken *between* push-admission and apply (mid-round, inbox
        non-empty), the snapshot still restores to a state from which
        training re-converges bit-identically: params/optimizer state are
        pre-round by construction (the inbox has not been applied), and
        the per-worker clocks are rolled back for every in-flight push —
        those streams die with the crash, so the restored run replays
        them.  Chunk-by-chunk staged pushes never advanced a clock, so
        discarding them needs no rollback."""
        wc = self.worker_clock.copy()
        for w in self._inbox:
            wc[w] -= 1
        return {
            "params": np.asarray(self.params),
            "state": tuple(np.asarray(r.reshape(-1)) for r in (
                self._assemble_rows(lambda s, k=k: s.state[k])
                for k in range(self.spec.num_state_slots)
            )),
            "step": self.step,
            "worker_clock": wc,
            # fault-tier metadata (legacy snapshots without these restore
            # to an all-alive fabric — see restore)
            "dead_workers": np.asarray(sorted(self.dead_workers),
                                       dtype=np.int64),
            "replication": self.replication,
        }

    def restore(self, snap: dict) -> None:
        """Restore a snapshot: parameters, optimizer state, the round
        counter AND the per-worker clocks.  Restoring the clocks matters:
        SSP admission and late-push dropping both compare ``worker_clock``
        against ``step``, so resuming on pre-restore clocks would admit (or
        drop) the wrong pushes.  Legacy snapshots without ``worker_clock``
        — and elastic restores onto a different worker count — reset every
        worker to the restored step.  Partially staged pushes and codec
        error-feedback residuals are discarded: they belong to in-flight
        streams that did not survive the restore."""
        shape = (self.space.num_chunks, self.space.chunk_elems)
        rows = jnp.asarray(snap["params"], jnp.float32).reshape(shape)
        state_rows = [
            jnp.asarray(s, jnp.float32).reshape(shape) for s in snap["state"]
        ]
        for shard in self.shards:
            ids = jnp.asarray(shard.chunk_ids)
            shard.params = rows[ids]
            shard.state = tuple(r[ids] for r in state_rows)
        self.step = int(snap["step"])
        wc = snap.get("worker_clock")
        if wc is not None and len(np.atleast_1d(wc)) == self.num_workers:
            self.worker_clock = np.asarray(wc, dtype=np.int64).copy()
        else:
            self.worker_clock = np.full(self.num_workers, self.step,
                                        dtype=np.int64)
        # every worker resumes against the restored params version
        self._pull_step = np.full(self.num_workers, self.step,
                                  dtype=np.int64)
        self._drops_since_step = 0
        self._inbox.clear()
        self._staged.clear()
        self._deferred.clear()
        for rack in self.rack_aggs:
            rack.reset()  # also revives an attached ToR switch pool
        if self.core_switch is not None:
            self.core_switch.reset()
            self._core_ef = init_ef_state(self.compression,
                                          self.space.flat_elems)
        self._worker_ef = {
            w: init_ef_state(self.compression, self.space.flat_elems)
            for w in self._worker_ef
        }
        # fault tier: legacy snapshots (no replication metadata) restore
        # to an all-alive fabric; the fault cursor rewinds so a replayed
        # plan re-fires from the restored round (byte-for-byte replay),
        # and the trace drops the rolled-back tail so replayed events
        # re-append exactly once — export_fault_trace stays the current
        # timeline's record, never a mix of both passes.  (ServerStats
        # stays cumulative across the replay, like every other stat.)
        self.fault_trace = [r for r in self.fault_trace
                            if r["round"] <= self.step]
        dead = snap.get("dead_workers")
        self.dead_workers = (
            {int(w) for w in np.atleast_1d(dead) if 0 <= w < self.num_workers}
            if dead is not None else set()
        )
        self._link_degrade.clear()
        self._fault_cursor = self.step
        self._switch_cursor = self.step
        for group, shard in zip(self.replicas, self.shards):
            group.sync(shard, round_=self.step)  # provisioning, not wire
        # serving caches stamped with rounds from the abandoned timeline
        # must never serve again (the restored counter may rewind past
        # them, and the same round number will hold different bits);
        # dead planes are pruned as a side effect
        self.read_planes = [r for r in self.read_planes if r() is not None]
        for ref in self.read_planes:
            plane = ref()
            if plane is not None:
                plane.invalidate()
        # sparse tiers' serving caches are version-stamped the same way
        self.sparse_tiers = [r for r in self.sparse_tiers
                             if r() is not None]
        for ref in self.sparse_tiers:
            tier = ref()
            if tier is not None:
                tier.on_restore()
        self._flat_cache = None

    # -- introspection -----------------------------------------------------
    def rack_of(self, worker: int) -> int:
        """Rack hosting ``worker`` (0 when no topology is attached)."""
        return self.topology.rack_of[worker] if self.topology else 0

    def global_chunk_ids(self, local_ids: np.ndarray | None = None) -> np.ndarray:
        """Map local chunk ids into the fabric-wide namespace
        (``chunk_base`` offset; identity on a dedicated fabric)."""
        if local_ids is None:
            local_ids = np.arange(self.space.num_chunks)
        ids = np.asarray(local_ids, dtype=np.int64)
        if ids.size and (ids.min() < 0 or ids.max() >= self.space.num_chunks):
            raise ValueError("local chunk id out of range")
        return ids + self.chunk_base

    def describe(self) -> str:
        lines = [
            (f"[{self.namespace}] " if self.namespace else "")
            + f"PBoxFabric: {self.num_shards} shards x "
            f"{self.space.num_chunks} chunks ({self.space.chunk_elems} elems), "
            f"mode={self.mode}, workers={self.num_workers}, "
            f"codec={self.compression.codec}"
        ]
        # the full knob surface, round-tripped from the one config object
        # every fabric now carries (core/config.py) — nothing is omitted
        # the way ad-hoc lines used to omit newer knobs
        lines += ["  " + ln for ln in self.config.describe().splitlines()]
        if self.switch_cfg.enabled:
            s = self.stats
            lines.append(
                f"  switch tier: {s.switch_rounds} rounds offloaded "
                f"({s.switch_fallback_rounds} fell back, "
                f"{s.core_switch_rounds} core-pooled), "
                f"{s.bytes_switch_agg >> 10} KiB absorbed in-pool, "
                f"{s.bytes_switch_saved >> 10} KiB ingress saved"
            )
            for rack in self.rack_aggs:
                if rack.switch is not None:
                    lines.append("    " + rack.switch.describe())
            if self.core_switch is not None:
                lines.append("    " + self.core_switch.describe())
        if self.topology is not None:
            lines.append("  " + self.topology.describe())
            lines.append(
                f"  core link: {self.stats.bytes_core_link >> 10} KiB in "
                f"{self.stats.rack_streams} aggregated streams, rack links "
                f"{self.stats.bytes_rack_link >> 10} KiB, late pushes "
                f"dropped {self.stats.late_pushes_dropped}"
            )
        if self.replication > 1:
            s = self.stats
            lines.append(
                f"  replication: R={self.replication}, "
                f"{s.bytes_replication >> 10} KiB chained, "
                f"{s.failovers} failovers ({s.resilvers} re-silvered), "
                f"{len(self.dead_workers)} workers down"
            )
        for ref in self.read_planes:
            plane = ref()
            if plane is not None:
                lines.append("  " + plane.describe())
        for shard in self.shards:
            lines.append(
                f"  shard {shard.shard_id}: {shard.num_chunks} chunks, "
                f"pushed={shard.stats.bytes_pushed >> 10} KiB, "
                f"pulled={shard.stats.bytes_pulled >> 10} KiB"
            )
        return "\n".join(lines)


# ---------------------------------------------------------------------------
# worker harness
# ---------------------------------------------------------------------------
class WorkerHarness:
    """Drives K logical workers against a PBoxFabric (or a tenancy
    ``JobHandle``, which exposes the same worker API — the harness is how
    one tenant's workers drive the shared box).

    ``grad_fn(params_tree, batch) -> grad_tree`` is the worker compute;
    ``speed[w]`` scales how many scheduler ticks worker w needs per step
    (straggler modelling); ``chunk_groups > 1`` streams each push in that
    many chunk groups through the fabric's staging path (chunk-by-chunk
    push, as on a real NIC).

    Workers carry the fabric's rack assignment (``NetworkTopology``):
    ``rack_of(w)`` exposes it and ``steps_done_by_rack()`` summarizes
    progress per rack, so straggler experiments can slow a whole rack
    (``speed_by_rack``) instead of hand-listing workers.
    """

    def __init__(
        self,
        server: PBoxFabric,
        grad_fn: Callable,
        batches_fn: Callable[[int, int], Any],  # (worker, step) -> batch
        speed: list[int] | None = None,
        chunk_groups: int = 1,
        speed_by_rack: dict[int, int] | None = None,
    ):
        self.server = server
        self.grad_fn = grad_fn
        self.batches_fn = batches_fn
        k = server.num_workers
        self.topology = server.topology
        self.speed = list(speed) if speed else [1] * k
        if speed_by_rack:
            if self.topology is None:
                raise ValueError("speed_by_rack needs a fabric topology")
            bad = [r for r in speed_by_rack if not
                   0 <= r < self.topology.num_racks]
            if bad:
                raise ValueError(
                    f"speed_by_rack names racks {bad} but the topology has "
                    f"racks 0..{self.topology.num_racks - 1}"
                )
            for w in range(k):
                r = self.topology.rack_of[w]
                if r in speed_by_rack:
                    self.speed[w] = speed_by_rack[r]
        self.chunk_groups = chunk_groups
        self._phase = [0] * k
        self.steps_done = [0] * k

    def rack_of(self, worker: int) -> int:
        return self.server.rack_of(worker)

    @property
    def job(self) -> str | None:
        """Tenant namespace this harness drives (None on a dedicated
        fabric)."""
        return getattr(self.server, "namespace", None)

    def telemetry(self) -> dict:
        """Job-level progress snapshot: worker steps, simulated per-round
        time (what co-tenancy inflates), and wire totals."""
        s = self.server.stats
        return {
            "job": self.job,
            "worker_steps": list(self.steps_done),
            "server_steps": s.steps,
            "sim_step_us": s.sim_pipelined_us / max(1, s.steps),
            "sim_core_wire_us": s.sim_core_wire_us,
            "bytes_pushed": s.bytes_pushed,
            "bytes_pulled": s.bytes_pulled,
            "steps_done_by_rack": self.steps_done_by_rack(),
        }

    def steps_done_by_rack(self) -> dict[int, int]:
        """Total completed worker-steps per rack (rack 0 holds everyone
        when the fabric has no topology)."""
        out: dict[int, int] = {}
        for w, n in enumerate(self.steps_done):
            out[self.rack_of(w)] = out.get(self.rack_of(w), 0) + n
        return out

    def _push(self, w: int, gflat: jax.Array) -> None:
        srv = self.server
        if self.chunk_groups <= 1:
            srv.push(w, gflat)
            return
        rows = gflat.reshape(srv.space.num_chunks, srv.space.chunk_elems)
        for ids in np.array_split(np.arange(srv.space.num_chunks),
                                  self.chunk_groups):
            if len(ids):
                srv.push_chunks(w, ids, rows[jnp.asarray(ids)])

    def tick(self) -> None:
        """One scheduler tick: every non-blocked worker advances."""
        srv = self.server
        for w in range(srv.num_workers):
            if not srv.can_proceed(w):
                continue
            self._phase[w] += 1
            if self._phase[w] < self.speed[w]:
                continue
            self._phase[w] = 0
            flat = srv.pull(w)
            params = srv.space.unflatten(flat)
            batch = self.batches_fn(w, self.steps_done[w])
            grads = self.grad_fn(params, batch)
            self._push(w, srv.space.flatten(grads))
            self.steps_done[w] += 1

    def _alive_progress(self) -> list[int]:
        """Completed steps of the workers still alive (fault tier: a
        crashed worker's stalled count must not hold ``run`` hostage)."""
        is_alive = getattr(self.server, "alive", None)
        if is_alive is None:
            return list(self.steps_done)
        alive = [d for w, d in enumerate(self.steps_done) if is_alive(w)]
        if not alive:
            raise RuntimeError("every worker has crashed; nothing can run")
        return alive

    def run(self, worker_steps: int) -> None:
        guard = 0
        while min(self._alive_progress()) < worker_steps:
            self.tick()
            guard += 1
            if guard > worker_steps * max(self.speed) * 10 + 100:
                raise RuntimeError("scheduler livelock — staleness deadlock?")
