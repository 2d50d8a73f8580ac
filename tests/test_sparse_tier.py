"""Property tests for the sparse tier (core/sparse.py + the sparse half of
core/serving.py): placement planning, the jagged batch format, kernel
bit-identity, sharding-independent training, codec + error feedback, exact
byte accounting, hot-row serving, and failover.

The headline invariants (ISSUE 6):

  * sharded training == single-table training, bit-for-bit, across
    {1,2,8} shards x {1,2,4} racks x {none,bf16,int8} codecs;
  * a cached serving read == a direct table read at the stamped version.

Property tests run through hypothesis when installed, else the
deterministic fixed-seed fallback (tests/_hypo_fallback.py).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    import hypothesis.strategies as st
    from hypothesis import given, settings
except ImportError:  # optional dep: fixed-seed stand-in, no shrinking
    from _hypo_fallback import given, settings, st

from repro.core.replication import ShardLost
from repro.core.serving import SparseReadPlane, zipfian_trace
from repro.core.sparse import (
    RowPlacement,
    SparseTier,
    check_jagged,
    encode_rows,
    row_wire_bytes,
)
from repro.core.topology import NetworkTopology
from repro.kernels.embedding_bag.ops import embedding_bag
from repro.kernels.embedding_bag.ref import embedding_bag_ref
from repro.models.recsys.embedding import jagged_to_padded
from repro.runtime.sparse_push import coalesce_ids_rows

V, D, K = 64, 16, 2  # default vocab rows, embedding dim, workers
RNG = np.random.default_rng(1805)
INIT = RNG.standard_normal((V, D)).astype(np.float32)


def make_tier(num_shards=2, *, racks=0, codec="none", replication=1,
              placement="hash", workers=K, lr=0.1, init=INIT):
    topo = (NetworkTopology(num_workers=max(workers, racks),
                            num_racks=racks) if racks else None)
    tier = SparseTier(num_shards=num_shards, num_workers=workers,
                      topology=topo, codec=codec, replication=replication,
                      placement=placement, lr=lr)
    tier.add_table("t0", init)
    return tier


def drive(tier, rounds=3, seed=5, batch=12, workers=K, vocab=V):
    """Push ``rounds`` deterministic sparse-gradient rounds."""
    rng = np.random.default_rng(seed)
    for _ in range(rounds):
        for w in range(workers):
            ids = rng.integers(0, vocab, size=batch)
            g = rng.standard_normal((batch, D)).astype(np.float32)
            tier.push(w, {"t0": (ids, g)})
    return tier


def jagged_batch(rng, nbags, vocab, max_len):
    """A random jagged batch including empty bags and duplicate ids."""
    lens = rng.integers(0, max_len + 1, size=nbags)
    values = rng.integers(0, vocab, size=int(lens.sum()))
    offsets = np.concatenate([[0], np.cumsum(lens)])
    return values.astype(np.int64), offsets.astype(np.int64)


# ---------------------------------------------------------------------------
# placement planner
# ---------------------------------------------------------------------------
def test_placement_range_contiguous_and_balanced():
    plan = RowPlacement(101, 8, "range")
    # contiguous blocks: owner is non-decreasing
    assert (np.diff(plan.owner) >= 0).all()
    sizes = [len(r) for r in plan.shard_rows]
    assert max(sizes) - min(sizes) <= 1
    assert sum(sizes) == 101
    assert plan.balance <= 1.1


def test_placement_hash_covers_and_is_deterministic():
    a = RowPlacement(512, 8, "hash")
    b = RowPlacement(512, 8, "hash")
    np.testing.assert_array_equal(a.owner, b.owner)
    # every row owned exactly once, no shard starved at V >> S
    assert sum(len(r) for r in a.shard_rows) == 512
    assert all(len(r) > 0 for r in a.shard_rows)
    # local_of inverts shard_rows
    for s in range(8):
        rows = a.shard_rows[s]
        np.testing.assert_array_equal(rows[a.local_of(s, rows)], rows)


def test_placement_replica_racks_anti_affine():
    topo = NetworkTopology(num_workers=8, num_racks=4)
    tier = SparseTier(num_shards=4, num_workers=2, topology=topo,
                      replication=3)
    for s in range(4):
        racks = tier.chain_racks[s]
        assert len(set(int(r) for r in racks)) == 3  # factor <= num_racks
    np.testing.assert_array_equal(tier.home_racks,
                                  topo.home_racks(4))


def test_placement_rejects_unknown_policy_and_bad_shapes():
    with pytest.raises(ValueError):
        RowPlacement(16, 2, "round-robin")
    with pytest.raises(ValueError):
        RowPlacement(4, 8)  # more shards than rows
    with pytest.raises(ValueError):
        SparseTier(num_shards=1, placement="modulo")


# ---------------------------------------------------------------------------
# jagged batch format
# ---------------------------------------------------------------------------
@settings(max_examples=25, deadline=None)
@given(nbags=st.integers(1, 8), max_len=st.integers(0, 6),
       seed=st.integers(0, 10_000))
def test_jagged_to_padded_preserves_bags(nbags, max_len, seed):
    rng = np.random.default_rng(seed)
    values, offsets = jagged_batch(rng, nbags, V, max_len)
    idx, w = jagged_to_padded(values, offsets)
    assert idx.shape == w.shape and idx.shape[0] == nbags
    lens = np.diff(offsets)
    for b in range(nbags):
        n = int(lens[b])
        np.testing.assert_array_equal(np.asarray(idx)[b, :n],
                                      values[offsets[b]:offsets[b + 1]])
        # padded slots carry zero weight (empty bags: all-zero row)
        assert (np.asarray(w)[b, n:] == 0).all()
        assert (np.asarray(w)[b, :n] == 1).all()


def test_jagged_empty_bags_lookup_to_zero():
    tier = make_tier(2)
    out = tier.lookup(0, "t0", np.array([], np.int64),
                      np.array([0, 0, 0], np.int64))
    assert out.shape == (2, D)
    np.testing.assert_array_equal(np.asarray(out), 0.0)


def test_jagged_duplicate_ids_within_bag_accumulate():
    tier = make_tier(2)
    out = tier.lookup(0, "t0", np.array([7, 7, 7]), np.array([0, 3]))
    expect = 3.0 * np.asarray(tier.table("t0"))[7]
    np.testing.assert_allclose(np.asarray(out)[0], expect, rtol=1e-6)


def test_jagged_bad_offsets_rejected():
    tier = make_tier(2)
    vals = np.array([1, 2, 3])
    for bad in (np.array([0, 2]),  # doesn't span values
                np.array([1, 3]),  # doesn't start at 0
                np.array([0, 2, 1, 3]),  # non-monotone
                np.array([0.0, 3.0])):  # float offsets
        with pytest.raises((ValueError, TypeError)):
            tier.lookup(0, "t0", vals, bad)
    with pytest.raises(ValueError):
        check_jagged(np.array([V + 3]), np.array([0, 1]), V)  # oob id
    with pytest.raises(TypeError):
        check_jagged(np.array([1.5]), np.array([0, 1]), V)  # float ids


# ---------------------------------------------------------------------------
# kernel / lookup bit-identity
# ---------------------------------------------------------------------------
@settings(max_examples=15, deadline=None)
@given(b=st.integers(1, 6), length=st.integers(1, 5),
       seed=st.integers(0, 10_000))
def test_embedding_bag_pallas_matches_ref_bit_exact(b, length, seed):
    rng = np.random.default_rng(seed)
    table = jnp.asarray(rng.standard_normal((V, D)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, V, (b, length)), jnp.int32)
    w = jnp.asarray(rng.standard_normal((b, length)), jnp.float32)
    for mode in ("sum", "mean"):
        out_k = embedding_bag(table, idx, w, mode, use_pallas=True)
        out_r = embedding_bag_ref(table, idx, w, mode)
        np.testing.assert_array_equal(np.asarray(out_k), np.asarray(out_r))


def test_embedding_bag_matches_slot_order_fold():
    """The kernel's semantics is the slot-order left fold.  Bit-level the
    pinned contract is kernel == ref.py einsum (previous test — that is
    what the tier's sharding invariant rides on); against an *eager*
    fold the compiled kernel may contract multiply-adds (FMA), so this
    documents the fold semantics at FMA tolerance."""
    rng = np.random.default_rng(3)
    table = rng.standard_normal((V, D)).astype(np.float32)
    idx = rng.integers(0, V, (4, 5))
    w = rng.standard_normal((4, 5)).astype(np.float32)
    out = embedding_bag(jnp.asarray(table), jnp.asarray(idx),
                        jnp.asarray(w), "sum", use_pallas=True)
    fold = np.zeros((4, D), np.float32)
    for length in range(5):  # slot-order left fold
        fold += w[:, length, None] * table[idx[:, length]]
    np.testing.assert_allclose(np.asarray(out), fold, rtol=1e-6, atol=1e-6)


@settings(max_examples=10, deadline=None)
@given(shards=st.sampled_from([1, 2, 8]),
       policy=st.sampled_from(["hash", "range"]),
       seed=st.integers(0, 10_000))
def test_lookup_sharded_bit_identical_to_single(shards, policy, seed):
    rng = np.random.default_rng(seed)
    values, offsets = jagged_batch(rng, 5, V, 4)
    weights = rng.standard_normal(values.size).astype(np.float32)
    single = make_tier(1)
    sharded = make_tier(shards, placement=policy)
    for mode in ("sum", "mean"):
        a = single.lookup(0, "t0", values, offsets, weights, mode=mode)
        b = sharded.lookup(0, "t0", values, offsets, weights, mode=mode)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_lookup_out_of_range_rejected():
    tier = make_tier(2)
    with pytest.raises(ValueError):
        tier.lookup(0, "t0", np.array([V]), np.array([0, 1]))
    with pytest.raises(ValueError):
        tier.lookup(0, "t0", np.array([-1]), np.array([0, 1]))


# ---------------------------------------------------------------------------
# embedding_bag ops validation (the ISSUE's silent-garbage fix)
# ---------------------------------------------------------------------------
def test_ops_rejects_float_indices():
    table = jnp.zeros((4, 8))
    with pytest.raises(TypeError):
        embedding_bag(table, jnp.asarray([[0.5]]), jnp.ones((1, 1)), "sum")


def test_ops_rejects_out_of_range_concrete_indices():
    """Regression: an out-of-range row used to stream garbage silently
    through the Pallas prefetch index_map."""
    table = jnp.arange(32.0).reshape(4, 8)
    for bad in ([[4]], [[-1]], [[99]]):
        with pytest.raises(ValueError):
            embedding_bag(table, jnp.asarray(bad), jnp.ones((1, 1)), "sum",
                          use_pallas=True)
    with pytest.raises(ValueError):
        embedding_bag(table, jnp.asarray([[0]]), jnp.ones((1, 1)), "max")


def test_ops_clips_under_trace_matching_gather_semantics():
    """Inside jit the indices are unknowable: the wrapper clamps into
    [0, V) (lookup_fields' convention) instead of failing."""
    table = jnp.asarray(np.arange(32.0, dtype=np.float32).reshape(4, 8))

    @jax.jit
    def f(idx):
        return embedding_bag(table, idx, jnp.ones((1, 1)), "sum")

    np.testing.assert_array_equal(np.asarray(f(jnp.asarray([[99]]))),
                                  np.asarray(table[3:4]))
    np.testing.assert_array_equal(np.asarray(f(jnp.asarray([[-7]]))),
                                  np.asarray(table[0:1]))


# ---------------------------------------------------------------------------
# update path: sharding-independent training
# ---------------------------------------------------------------------------
def dense_sgd_reference(table, pushes, lr):
    """Oracle: per round, scatter every worker's coalesced rows into a
    dense gradient (worker-order fold) and step touched rows."""
    t = np.asarray(table, np.float64).copy().astype(np.float32)
    for round_pushes in pushes:
        grad = np.zeros_like(t)
        for ids, rows in round_pushes:  # ascending worker order
            np.add.at(grad, ids, rows)
        touched = np.unique(np.concatenate(
            [ids for ids, _ in round_pushes]))
        t[touched] -= (lr / len(round_pushes)) * grad[touched]
    return t


def test_single_shard_matches_dense_scatter_reference():
    tier = make_tier(1, lr=0.1)
    rng = np.random.default_rng(5)
    pushes = []
    for _ in range(3):
        rp = []
        for w in range(K):
            ids = rng.integers(0, V, size=12)
            g = rng.standard_normal((12, D)).astype(np.float32)
            tier.push(w, {"t0": (ids, g)})
            u, s = coalesce_ids_rows(ids, jnp.asarray(g))
            rp.append((u, np.asarray(s)))
        pushes.append(rp)
    ref = dense_sgd_reference(INIT, pushes, 0.1)
    np.testing.assert_allclose(np.asarray(tier.table("t0")), ref,
                               rtol=1e-6, atol=1e-7)
    # untouched rows bit-untouched (lazy sparse SGD)
    touched = np.unique(np.concatenate(
        [ids for rp in pushes for ids, _ in rp]))
    cold = np.setdiff1d(np.arange(V), touched)
    np.testing.assert_array_equal(np.asarray(tier.table("t0"))[cold],
                                  INIT[cold])


@pytest.mark.parametrize("codec", ["none", "bf16", "int8"])
@pytest.mark.parametrize("racks", [1, 2, 4])
@pytest.mark.parametrize("shards", [2, 8])
def test_sharded_training_bit_identical_to_single_table(shards, racks,
                                                        codec):
    """THE headline invariant: {1,2,8} shards x {1,2,4} racks x
    {none,bf16,int8} all produce byte-identical tables."""
    single = drive(make_tier(1, codec=codec))
    sharded = drive(make_tier(shards, racks=racks, codec=codec))
    np.testing.assert_array_equal(np.asarray(single.table("t0")),
                                  np.asarray(sharded.table("t0")))
    np.testing.assert_array_equal(single.row_versions("t0"),
                                  sharded.row_versions("t0"))


@settings(max_examples=8, deadline=None)
@given(shards=st.sampled_from([2, 8]),
       policy=st.sampled_from(["hash", "range"]),
       seed=st.integers(0, 10_000))
def test_sharded_training_property_sweep(shards, policy, seed):
    a = drive(make_tier(1), seed=seed)
    b = drive(make_tier(shards, placement=policy), seed=seed)
    np.testing.assert_array_equal(np.asarray(a.table("t0")),
                                  np.asarray(b.table("t0")))


def test_duplicate_push_ids_coalesce_on_the_wire():
    """Duplicate ids fold at the NIC: same math, fewer routed rows."""
    dup = make_tier(2, workers=1)
    ids = np.array([3, 3, 3, 9, 9])
    rows = np.arange(5 * D, dtype=np.float32).reshape(5, D)
    dup.push(0, {"t0": (ids, rows)})
    assert dup.stats.rows_pushed == 2
    assert dup.stats.rows_coalesced == 3
    assert dup.stats.bytes_pushed == row_wire_bytes("none", D, 2)
    flat = make_tier(2, workers=1)
    flat.push(0, {"t0": (np.array([3, 9]),
                         np.stack([rows[:3].sum(0), rows[3:].sum(0)]))})
    np.testing.assert_allclose(np.asarray(dup.table("t0")),
                               np.asarray(flat.table("t0")),
                               rtol=1e-6, atol=1e-6)


def test_push_rejects_bad_ids_and_shapes():
    tier = make_tier(2)
    with pytest.raises(ValueError):
        tier.push(0, {"t0": (np.array([V]), np.zeros((1, D)))})
    with pytest.raises(ValueError):
        tier.push(0, {"t0": (np.array([0]), np.zeros((1, D + 1)))})
    with pytest.raises(TypeError):
        tier.push(0, {"t0": (np.array([0.5]), np.zeros((1, D)))})
    with pytest.raises(KeyError):
        tier.push(0, {"nope": (np.array([0]), np.zeros((1, D)))})
    tier.push(0, {"t0": (np.array([1]), np.ones((1, D)))})
    with pytest.raises(RuntimeError):  # double push inside one round
        tier.push(0, {"t0": (np.array([2]), np.ones((1, D)))})


def test_row_codec_error_feedback_compensates():
    """int8 EF: over many rounds of a constant row gradient (with spread
    — a flat row quantizes exactly), the accumulated update tracks the
    exact SGD trajectory: the residual carries each round's rounding
    error forward instead of re-losing it every round."""
    g = (0.003 * (1.0 + 0.37 * np.arange(D))).astype(np.float32)[None, :]
    lr = 1.0
    with_ef = SparseTier(num_shards=1, num_workers=1, codec="int8",
                         error_feedback=True, lr=lr)
    with_ef.add_table("t0", np.zeros((V, D), np.float32))
    no_ef = SparseTier(num_shards=1, num_workers=1, codec="int8",
                       error_feedback=False, lr=lr)
    no_ef.add_table("t0", np.zeros((V, D), np.float32))
    rounds = 50
    for _ in range(rounds):
        with_ef.push(0, {"t0": (np.array([4]), g)})
        no_ef.push(0, {"t0": (np.array([4]), g)})
    exact = -lr * rounds * g[0]
    err_ef = np.abs(np.asarray(with_ef.table("t0"))[4] - exact).max()
    err_raw = np.abs(np.asarray(no_ef.table("t0"))[4] - exact).max()
    quant_step = float(np.abs(g).max()) / 127.0
    assert err_ef <= 2 * quant_step  # bounded, round count independent
    assert err_ef < err_raw  # strictly better than dropping the error


def test_encode_rows_zero_row_and_error_bound():
    rows = jnp.asarray(np.vstack([np.zeros((1, D)),
                                  np.full((1, D), 3.7)]), jnp.float32)
    dec = np.asarray(encode_rows("int8", rows))
    np.testing.assert_array_equal(dec[0], 0.0)  # zero row -> scale 1.0
    amax = 3.7
    assert np.abs(dec[1] - 3.7).max() <= amax / 254 + 1e-7
    with pytest.raises(ValueError):
        encode_rows("fp4", rows)


# ---------------------------------------------------------------------------
# exact byte accounting
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("codec,per_row", [
    ("none", 4 * D + 4), ("bf16", 2 * D + 4), ("int8", D + 4 + 4)])
def test_push_wire_bytes_exact(codec, per_row):
    """Closed-form check: every unique routed row costs payload + id, and
    the rack/core split follows worker rack vs owner home rack."""
    topo = NetworkTopology(num_workers=2, num_racks=2)
    tier = SparseTier(num_shards=2, num_workers=1, topology=topo,
                      codec=codec, placement="range")
    tier.add_table("t0", INIT)
    # range placement over V=64: shard 0 owns [0,32), shard 1 owns [32,64)
    # worker 0 sits in rack 0; shard homes are racks 0 and 1
    ids = np.array([1, 2, 40, 41, 42])
    tier.push(0, {"t0": (ids, np.ones((5, D), np.float32))})
    assert tier.stats.bytes_pushed == 5 * per_row
    assert tier.stats.bytes_rack_link == 2 * per_row  # rows 1,2 -> shard 0
    assert tier.stats.bytes_core_link == 3 * per_row  # rows 40..42 cross
    assert tier.stats.sim_push_us > 0


def test_lookup_wire_bytes_exact_per_unique_row():
    topo = NetworkTopology(num_workers=2, num_racks=2)
    tier = SparseTier(num_shards=2, num_workers=2, topology=topo,
                      placement="range")
    tier.add_table("t0", INIT)
    per_row = 4 * D + 4  # pulls are raw f32 + id, never codec'd
    tier.lookup(0, "t0", np.array([1, 1, 1, 40]), np.array([0, 4]))
    assert tier.stats.rows_pulled == 2  # unique rows only
    assert tier.stats.bytes_pulled == 2 * per_row
    assert tier.stats.bytes_rack_link == per_row  # row 1: rack-local
    assert tier.stats.bytes_core_link == per_row  # row 40: cross-rack
    assert tier.stats.sim_lookup_us > 0


def test_replication_ships_only_delta_rows():
    topo = NetworkTopology(num_workers=2, num_racks=2)
    tier = SparseTier(num_shards=2, num_workers=1, topology=topo,
                      replication=2, placement="range")
    tier.add_table("t0", INIT)
    tier.push(0, {"t0": (np.array([1, 40]), np.ones((2, D), np.float32))})
    # one updated row per shard, one chain hop each, raw f32 + id
    assert tier.stats.rows_replicated == 2
    assert tier.stats.bytes_replicated == 2 * (4 * D + 4)


# ---------------------------------------------------------------------------
# hot-row serving
# ---------------------------------------------------------------------------
@settings(max_examples=6, deadline=None)
@given(skew=st.sampled_from([0.0, 0.8, 1.2]), seed=st.integers(0, 1000))
def test_cached_reads_bit_identical_to_direct(skew, seed):
    """Headline serving invariant: under a Zipfian trace interleaved with
    training rounds, every served row equals the direct table read."""
    tier = make_tier(4, racks=2, replication=2)
    plane = SparseReadPlane(tier, num_frontends=2, cache_rows=24)
    trace = zipfian_trace(V, 120, skew, seed=seed)
    rng = np.random.default_rng(seed)
    for step in range(6):
        ids = trace[step * 20:(step + 1) * 20]
        res = plane.read_rows(step % 2, "t0", ids)
        direct = np.asarray(tier.table("t0"))[ids]
        np.testing.assert_array_equal(np.asarray(res.rows), direct)
        np.testing.assert_array_equal(res.versions,
                                      tier.row_versions("t0")[ids])
        drive(tier, rounds=1, seed=int(rng.integers(1 << 30)), batch=6)


def test_row_update_invalidates_exactly_the_updated_rows():
    tier = make_tier(2, workers=K)
    plane = SparseReadPlane(tier, cache_rows=V)
    plane.read_rows(0, "t0", np.arange(V))  # warm every row
    assert plane.read_rows(0, "t0", np.arange(V)).hits.all()
    for w in range(K):
        tier.push(w, {"t0": (np.array([5, 9]),
                             np.ones((2, D), np.float32))})
    res = plane.read_rows(0, "t0", np.arange(V))
    assert not res.hits[5] and not res.hits[9]
    assert res.hits.sum() == V - 2
    assert plane.stats.stale_rows == 2


def test_hot_cache_lru_eviction_keeps_hot_head():
    tier = make_tier(2)
    plane = SparseReadPlane(tier, cache_rows=4)
    plane.read_rows(0, "t0", np.array([0, 1, 2, 3]))
    plane.read_rows(0, "t0", np.array([0, 1]))  # touch -> most recent
    plane.read_rows(0, "t0", np.array([50, 51]))  # evicts 2 and 3
    assert plane.stats.evictions == 2
    res = plane.read_rows(0, "t0", np.array([0, 1, 2]))
    assert res.hits[0] and res.hits[1] and not res.hits[2]


def test_serving_reads_never_perturb_training():
    served = make_tier(2, racks=2, replication=2)
    plane = SparseReadPlane(served, num_frontends=2, cache_rows=16)
    bare = make_tier(2, racks=2, replication=2)
    rng = np.random.default_rng(11)
    for r in range(3):
        plane.read_rows(r % 2, "t0", zipfian_trace(V, 30, 1.0, seed=r))
        seed = int(rng.integers(1 << 30))
        drive(served, rounds=1, seed=seed)
        drive(bare, rounds=1, seed=seed)
    np.testing.assert_array_equal(np.asarray(served.table("t0")),
                                  np.asarray(bare.table("t0")))


def test_serving_routes_rack_local_replicas():
    """R=3 over 2 racks: every shard's chain wraps into both racks, so
    every frontend finds a backup in its own rack and refreshes never
    cross the core (locality-greedy ``serve_rack`` routing)."""
    topo = NetworkTopology(num_workers=2, num_racks=2)
    tier = SparseTier(num_shards=2, num_workers=1, topology=topo,
                      replication=3)
    tier.add_table("t0", INIT)
    plane = SparseReadPlane(tier, num_frontends=2, cache_rows=V)
    plane.read_rows(0, "t0", np.arange(V))
    plane.read_rows(1, "t0", np.arange(V))
    assert plane.stats.bytes_refreshed > 0
    assert plane.stats.bytes_core_link == 0
    assert plane.stats.row_misses == 2 * V
    # R=2 leaves exactly one backup — in the *other* rack — so the same
    # reads cross the core: the anti-affinity/locality trade is visible
    tier2 = SparseTier(num_shards=2, num_workers=1, topology=topo,
                       replication=2)
    tier2.add_table("t0", INIT)
    plane2 = SparseReadPlane(tier2, num_frontends=1, cache_rows=V)
    plane2.read_rows(0, "t0", np.arange(V))
    assert plane2.stats.bytes_core_link > 0


def test_serving_invalidate_and_oob():
    tier = make_tier(2)
    plane = SparseReadPlane(tier, cache_rows=8)
    plane.read_rows(0, "t0", np.array([1, 2]))
    plane.invalidate()
    assert not plane.read_rows(0, "t0", np.array([1, 2])).hits.any()
    with pytest.raises(ValueError):
        plane.read_rows(0, "t0", np.array([V]))
    with pytest.raises(ValueError):
        plane.read_rows(5, "t0", np.array([1]))
    with pytest.raises(ValueError):
        zipfian_trace(V, 10, -1.0)


# ---------------------------------------------------------------------------
# replication / failover / fabric integration
# ---------------------------------------------------------------------------
def test_failover_every_shard_bit_exact():
    base = drive(make_tier(4, racks=2, replication=2), rounds=4)
    for crash in range(4):
        tier = make_tier(4, racks=2, replication=2)
        drive(tier, rounds=2)
        tier.failover(crash)
        drive(tier, rounds=2, seed=50)
        # replay rounds 3-4 on the baseline's schedule
        ref = drive(make_tier(4, racks=2, replication=2), rounds=2)
        drive(ref, rounds=2, seed=50)
        np.testing.assert_array_equal(np.asarray(tier.table("t0")),
                                      np.asarray(ref.table("t0")))
        assert tier.stats.failovers == 1 and tier.stats.resilvers == 1


def test_failover_without_replica_raises_shard_lost():
    tier = drive(make_tier(2, replication=1), rounds=1)
    with pytest.raises(ShardLost):
        tier.failover(0)


def test_fabric_attached_tier_inherits_and_fails_over():
    """A tier attached to a live fabric co-resides with the dense shards:
    crash_shard fails both over; restore invalidates sparse caches."""
    from repro.core.chunking import TILE_ELEMS, ParamSpace
    from repro.core.fabric import PBoxFabric
    from repro.optim.optimizers import sgd

    topo = NetworkTopology(num_workers=2, num_racks=2)
    dense = {"w": jnp.zeros((2 * TILE_ELEMS,), jnp.float32)}
    space = ParamSpace.build(dense, chunk_elems=TILE_ELEMS)
    fab = PBoxFabric(space, sgd(0.1), space.flatten(dense), num_shards=2,
                     num_workers=2, topology=topo, replication=2)
    tier = SparseTier(fabric=fab, lr=0.1)
    tier.add_table("t0", INIT)
    assert tier.num_shards == 2 and tier.replication == 2
    assert tier.topology is topo
    drive(tier, rounds=2)
    before = np.asarray(tier.table("t0"))
    plane = SparseReadPlane(tier, cache_rows=8)
    plane.read_rows(0, "t0", np.array([1, 2]))
    snap = fab.snapshot()
    assert fab.crash_shard(0) == "failed_over"
    assert tier.stats.failovers == 1  # fabric hook reached the tier
    np.testing.assert_array_equal(np.asarray(tier.table("t0")), before)
    fab.restore(snap)
    assert not plane.read_rows(0, "t0", np.array([1, 2])).hits.any()


def test_tier_barrier_follows_fabric_dead_workers():
    from repro.core.chunking import TILE_ELEMS, ParamSpace
    from repro.core.fabric import PBoxFabric
    from repro.optim.optimizers import sgd

    dense = {"w": jnp.zeros((TILE_ELEMS,), jnp.float32)}
    space = ParamSpace.build(dense, chunk_elems=TILE_ELEMS)
    fab = PBoxFabric(space, sgd(0.1), space.flatten(dense), num_shards=1,
                     num_workers=3)
    tier = SparseTier(fabric=fab)
    tier.add_table("t0", INIT)
    fab.crash_worker(2)
    tier.push(0, {"t0": (np.array([1]), np.ones((1, D), np.float32))})
    assert tier.round == 0  # barrier not met: worker 1 still owed
    tier.push(1, {"t0": (np.array([2]), np.ones((1, D), np.float32))})
    assert tier.round == 1  # fires at the surviving population


def test_describe_smoke():
    tier = drive(make_tier(2, racks=2, codec="int8", replication=2))
    plane = SparseReadPlane(tier, cache_rows=8)
    plane.read_rows(0, "t0", np.array([1, 2, 3]))
    assert "SparseTier" in tier.describe()
    assert "SparseReadPlane" in plane.describe()
    assert tier.stats.coalesce_rate >= 0.0
    assert plane.stats.hit_rate == 0.0
