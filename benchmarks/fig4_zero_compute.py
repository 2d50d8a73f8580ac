"""Paper Figure 4: ZeroComputeEngine limit study.

The paper drives PBox with infinitely fast workers to find the exchange
ceiling (PCIe-to-memory bound).  Two analogues:

  * SPMD: exchange-only steps (no model compute) on the devices present,
    across gradient sizes and strategies, in this process (a child process
    could not reach a chip its parent holds); derived column reports
    achieved GB/s of aggregated gradient per step and the modeled
    per-device wire bytes (flat in worker count for pbox — the
    scalability claim).
  * Fabric: the in-process PBox fabric fed precomputed gradients (zero
    worker compute), swept over shard counts; the event-clock columns are
    the paper's Fig. 4 shape — pipelined makespan vs the monolithic
    store-and-forward baseline, shrinking as engines are added.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from benchmarks.common import emit


def _run_spmd_sweep() -> None:
    """Exchange-only steps per strategy on a ("pod", "data") mesh of every
    device present (two pods when the count is even)."""
    from repro.core.exchange import ExchangeConfig, PSExchange
    from repro.core.zero_compute import (
        init_zero_compute_state,
        make_zero_compute_step,
    )
    from repro.launch.mesh import make_mesh
    from repro.optim.optimizers import momentum

    n = len(jax.devices())
    pods = 2 if n % 2 == 0 else 1
    mesh = make_mesh((pods, n // pods), ("pod", "data"))
    for strat in ("allreduce", "pbox", "pbox_hier"):
        for flat in (1 << 20, 1 << 23):
            ex = PSExchange(momentum(0.1, 0.9), ExchangeConfig(strat),
                            ("pod", "data"),
                            "pod" if strat == "pbox_hier" else None)
            step = make_zero_compute_step(mesh, ex, flat)
            state = init_zero_compute_state(mesh, ex, flat)
            p, g = jnp.zeros((flat,)), jnp.ones((flat,))
            p, state = step(p, g, state)  # compile
            jax.block_until_ready(p)
            iters, t0 = 5, time.perf_counter()
            for _ in range(iters):
                p, state = step(p, g, state)
            jax.block_until_ready(p)
            us = (time.perf_counter() - t0) / iters * 1e6
            gbs = flat * 4 / (us / 1e6) / 1e9
            mb = ex.modeled_bytes(flat, pods, n // pods)
            wire = (mb["push"] + mb["pull"] + (mb["xpod"] or 0.0)) / 2**20
            emit(f"fig4/{strat}_flat={flat >> 20}M", us,
                 f"agg_GBps={gbs:.2f};wire_MiB_dev={wire:.1f};devices={n}")


def _run_fabric_sweep() -> None:
    """Zero-compute drive of the in-process fabric: precomputed gradients,
    shard-count scaling curve from the event clock."""
    from repro.core.chunking import ParamSpace
    from repro.core.config import FabricConfig, PlacementConfig, WireConfig
    from repro.core.fabric import LinkModel, PBoxFabric
    from repro.optim.optimizers import momentum

    k = 4
    flat_elems = 1 << 20
    params = {"w": jnp.zeros((flat_elems,), jnp.float32)}
    space = ParamSpace.build(params)
    grads = [jnp.full((space.flat_elems,), float(w + 1)) for w in range(k)]
    link = LinkModel(wire_us_per_chunk=0.2, agg_us_per_chunk=1.0)
    for n_shards in (1, 2, 4, 8, 16):
        fab = PBoxFabric(
            space, momentum(0.1, 0.9), space.flatten(params),
            config=FabricConfig(
                num_workers=k, num_shards=n_shards,
                wire=WireConfig(link=link),
                placement=PlacementConfig(policy="round_robin"),
            ),
        )
        for w in range(k):  # compile
            fab.push(w, grads[w])
        steps, t0 = 3, time.perf_counter()
        for _ in range(steps):
            for w in range(k):
                fab.push(w, grads[w])
        us = (time.perf_counter() - t0) / steps * 1e6
        st = fab.stats
        emit(
            f"fig4/fabric_shards={n_shards}", us,
            f"sim_pipelined_us={st.sim_pipelined_us/st.steps:.0f};"
            f"sim_serialized_us={st.sim_serialized_us/st.steps:.0f};"
            f"pipeline_speedup={st.pipeline_speedup:.2f}",
        )


def run() -> None:
    _run_spmd_sweep()
    _run_fabric_sweep()


if __name__ == "__main__":
    run()
