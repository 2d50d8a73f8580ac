"""ZeroComputeEngine: the paper's Fig. 4 limit study.

Simulates infinitely fast computation by running *only* the parameter
exchange: a step takes synthetic per-worker gradients and performs
push → aggregate+optimize → pull.  Used to (a) find the exchange-only
throughput ceiling, (b) audit collective bytes per strategy from lowered
HLO, (c) benchmark μs/step on CPU at small scale.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.exchange import PSExchange


def make_zero_compute_step(
    mesh,
    exchange: PSExchange,
    flat_elems: int,
):
    """Returns jit'd step(pflat, gflat, state) -> (pflat, state).

    pflat/gflat are globally replicated over worker axes (each worker has its
    own gradient values in practice; replication here is only a stand-in —
    the collective pattern and byte counts are identical).
    """
    wa = exchange.worker_axes
    n_owner = 1
    for a in exchange.owner_axes:
        n_owner *= mesh.shape[a]

    state_specs = {
        "slots": tuple(P(exchange.owner_axes) for _ in range(exchange.spec.num_state_slots)),
        "ef": P(exchange.owner_axes) if exchange.cfg.compression.codec != "none"
        and exchange.cfg.compression.error_feedback else None,
        "step": P(),
    }

    def body(pflat, gflat, state):
        new_p, new_state = exchange.device_update(gflat, pflat, state)
        return new_p, new_state

    shmap = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(), P(), state_specs),
        out_specs=(P(), state_specs),
        check_vma=False,
    )
    return jax.jit(shmap, donate_argnums=(0, 2))


def init_zero_compute_state(mesh, exchange: PSExchange, flat_elems: int):
    """Global-view initial state matching make_zero_compute_step's specs."""
    n_owner = 1
    for a in exchange.owner_axes:
        n_owner *= mesh.shape[a]
    slab = flat_elems if exchange.cfg.strategy == "allreduce" else flat_elems // n_owner
    glob = slab * max(n_owner, 1)
    slots = tuple(
        jnp.zeros((glob,), jnp.float32)
        for _ in range(exchange.spec.num_state_slots)
    )
    ef = None
    c = exchange.cfg.compression
    if c.codec != "none" and c.error_feedback:
        ef = jnp.zeros((glob,), jnp.float32)
    return {"slots": slots, "ef": ef, "step": jnp.zeros((), jnp.int32)}
