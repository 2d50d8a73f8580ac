"""Direct unit tests for the two-level collective schedules
(core/hierarchy.py): hierarchical psum/pmean/all-gather must equal their
flat lax counterparts on whatever device set the host offers.

The mesh adapts to ``jax.device_count()`` — one device degenerates to a
(1, 1) mesh (both stages still trace and run); an even count splits into
two pods.  The multi-host byte-savings claim is exercised separately in
tests/scripts/hier_and_zero_compute.py with a forced 8-device host.

Plus the geo read-plane ladder (``ReadTier``/``tier_ladder``/
``select_tier``): latency floors priced off the topology's own
``hop_cost``, and staleness-bound routing to the nearest satisfying tier.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.launch import mesh as meshlib
from repro.core.config import HierarchyConfig
from repro.core.hierarchy import (
    hierarchical_pmean,
    hierarchical_psum,
    select_tier,
    tier_ladder,
    two_level_all_gather,
)
from repro.core.topology import NetworkTopology


def make_mesh():
    n = jax.device_count()
    pods = 2 if n % 2 == 0 else 1
    return meshlib.make_mesh((pods, n // pods), ("pod", "data")), pods, n // pods


def sharded_rows(n, inner):
    # one row per device; row length divisible by the inner axis so the
    # reduce-scatter stage tiles evenly
    return jnp.arange(float(n * 4 * inner)).reshape(n, 4 * inner)


def test_hierarchical_psum_and_pmean_match_flat():
    mesh, pods, inner = make_mesh()
    n = pods * inner
    x = sharded_rows(n, inner)

    def f(xs):
        return (lax.psum(xs, ("pod", "data")),
                hierarchical_psum(xs, ("data",), "pod"),
                hierarchical_pmean(xs, ("data",), "pod"))

    g = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P(("pod", "data")),
                                 out_specs=(P(None), P(None), P(None)),
                                 check_vma=False))
    flat, hier, mean = g(x)
    assert hier.shape == flat.shape
    np.testing.assert_allclose(np.asarray(flat), np.asarray(hier), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(flat) / n, np.asarray(mean),
                               rtol=1e-6)


def test_hierarchical_psum_no_outer_axis_is_plain_psum():
    mesh, pods, inner = make_mesh()
    x = sharded_rows(pods * inner, inner)

    def f(xs):
        return (lax.psum(xs, "data"),
                hierarchical_psum(xs, ("data",), None),
                hierarchical_pmean(xs, ("data",), None))

    g = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P(("pod", "data")),
                                 out_specs=(P("pod"), P("pod"), P("pod")),
                                 check_vma=False))
    flat, hier, mean = g(x)
    np.testing.assert_array_equal(np.asarray(flat), np.asarray(hier))
    np.testing.assert_allclose(np.asarray(flat) / inner, np.asarray(mean),
                               rtol=1e-6)


def test_two_level_all_gather_matches_flat():
    mesh, pods, inner = make_mesh()
    n = pods * inner
    x = sharded_rows(n, inner)

    def f(xs):
        return (lax.all_gather(xs, ("pod", "data"), axis=0, tiled=True),
                two_level_all_gather(xs, ("data",), "pod", axis=0))

    g = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P(("pod", "data")),
                                 out_specs=(P(None), P(None)),
                                 check_vma=False))
    flat, staged = g(x)
    # pure data movement: inner-then-outer staging is pod-major like the
    # flat multi-axis gather, and bytes are never touched arithmetically
    np.testing.assert_array_equal(np.asarray(flat), np.asarray(staged))


def test_two_level_all_gather_no_outer_axis():
    mesh, pods, inner = make_mesh()
    x = sharded_rows(pods * inner, inner)

    def f(xs):
        return (lax.all_gather(xs, "data", axis=0, tiled=True),
                two_level_all_gather(xs, ("data",), None, axis=0))

    g = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P(("pod", "data")),
                                 out_specs=(P("pod"), P("pod")),
                                 check_vma=False))
    flat, staged = g(x)
    np.testing.assert_array_equal(np.asarray(flat), np.asarray(staged))


def test_hierarchical_psum_preserves_nd_shape():
    mesh, pods, inner = make_mesh()
    n = pods * inner
    x = jnp.arange(float(n * 2 * inner * 3)).reshape(n * 2, inner * 3)

    def f(xs):
        return hierarchical_psum(xs, ("data",), "pod")

    g = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P(("pod", "data")),
                                 out_specs=P(None),
                                 check_vma=False))
    out = g(x)
    assert out.shape == (2, inner * 3)  # per-device block shape survives


# ---------------------------------------------------------------------------
# the geo read-plane ladder
# ---------------------------------------------------------------------------
def ladder_cfg(**kw):
    base = dict(enabled=True, staleness_ladder=(0, 4, 16),
                frontends_per_tier=(1, 2, 3), geo_oversubscription=8.0)
    base.update(kw)
    return HierarchyConfig(**base)


def test_tier_ladder_prices_floors_off_hop_cost():
    topo = NetworkTopology(num_workers=4, num_racks=2, oversubscription=4.0)
    tiers = tier_ladder(ladder_cfg(), topology=topo, wire_us_per_chunk=1.5)
    assert [t.name for t in tiers] == ["rack", "cluster", "xcluster"]
    core = topo.hop_cost(0, 1)  # the oversubscribed core hop
    assert core == 4.0
    # the client is *outside*: cross-cluster is local (floor 0), cluster
    # one WAN hop inward, rack a WAN + core transit away
    assert tiers[2].latency_floor_us == 0.0
    assert tiers[1].latency_floor_us == pytest.approx(1.5 * 8.0)
    assert tiers[0].latency_floor_us == pytest.approx(1.5 * (8.0 + core))
    # floors are strictly distinct and ordered: farther == fresher
    floors = [t.latency_floor_us for t in tiers]
    assert floors[0] > floors[1] > floors[2]
    # staleness bounds and sizes carry through verbatim
    assert [t.max_staleness for t in tiers] == [0, 4, 16]
    assert [t.num_frontends for t in tiers] == [1, 2, 3]
    # refresh caps pay the same distances back toward the fabric: rack
    # refreshes are rack-local (uncapped), cluster crosses the core,
    # cross-cluster crosses core + WAN
    assert tiers[0].refresh_cap is None
    assert tiers[1].refresh_cap == pytest.approx(1.0 / core)
    assert tiers[2].refresh_cap == pytest.approx(1.0 / (core * 8.0))


def test_tier_ladder_without_topology_uses_unit_core():
    tiers = tier_ladder(ladder_cfg(geo_oversubscription=2.0))
    assert tiers[0].latency_floor_us == pytest.approx(2.0 + 1.0)
    assert tiers[1].latency_floor_us == pytest.approx(2.0)
    assert tiers[2].latency_floor_us == 0.0
    # a two-tier ladder: rack + xcluster, one WAN hop between them
    two = tier_ladder(ladder_cfg(staleness_ladder=(0, 8),
                                 frontends_per_tier=(1, 1)))
    assert [t.name for t in two] == ["rack", "xcluster"]
    assert two[0].latency_floor_us == pytest.approx(8.0)
    # deeper ladders name the middle tiers uniquely
    four = tier_ladder(ladder_cfg(staleness_ladder=(0, 2, 4, 8),
                                  frontends_per_tier=(1, 1, 1, 1)))
    assert [t.name for t in four] == ["rack", "cluster1", "cluster2",
                                      "xcluster"]


def test_select_tier_routes_to_nearest_satisfying_bound():
    tiers = tier_ladder(ladder_cfg())  # bounds 0 / 4 / 16
    # a strict read can only use the rack tier
    assert select_tier(tiers, 0) == 0
    # tolerance buys distance: anything in [4, 16) reaches the cluster
    # tier, 16+ the client-local cross-cluster tier
    assert select_tier(tiers, 3) == 0
    assert select_tier(tiers, 4) == 1
    assert select_tier(tiers, 15) == 1
    assert select_tier(tiers, 16) == 2
    assert select_tier(tiers, 10 ** 6) == 2
    with pytest.raises(ValueError):
        select_tier(tiers, -1)
    with pytest.raises(ValueError):
        select_tier(tiers[1:], 0)  # no tier bounds staleness at 0
