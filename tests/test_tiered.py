"""Tests for the sampled-core tier (backend="approx") and the tiered
serving index (backend="tiered"): rate=1.0 oracle equivalence against
the exact SoA engine, ARI floors at real sampling rates, the rescaled
core threshold k_s = max(1, round(k * rate)), deterministic splitmix64
sampling, sharded composition (S in {1, 2, 4} and the process
transport), and the async verifier's divergence gauge in an obs
snapshot."""

import numpy as np
import pytest

from repro.api import ClusterConfig, build_index, restore_index
from repro.core import adjusted_rand_index
from repro.core.approx import SampledCoreDBSCAN, is_sampled, sampled_mask
from repro.data import blobs

from test_api import assert_same_partition


def cfg8(**kw):
    base = dict(d=8, k=24, t=8, eps=0.5, seed=0)
    base.update(kw)
    return ClusterConfig(**base)


def stream(idx, X, batch=200, window=None, drop_every=2):
    """Insert X in batches with periodic sliding-window deletions."""
    rng = np.random.default_rng(7)
    ids, ptr = [], 0
    for bno, s in enumerate(range(0, len(X), batch)):
        ids += idx.insert_batch(X[s:s + batch])
        if window and len(ids) - ptr > window and bno % drop_every == 0:
            drop = len(ids) - ptr - window
            idx.delete_batch(ids[ptr:ptr + drop])
            ptr += drop
    live = ids[ptr:]
    return live, idx.labels(live)


# ---------------------------------------------------------------------- #
# deterministic sampling
# ---------------------------------------------------------------------- #
def test_sampled_mask_matches_scalar_and_is_deterministic():
    ids = np.arange(0, 5000, dtype=np.int64)
    for rate, seed in [(0.1, 0), (0.3, 5), (0.5, 123)]:
        m = sampled_mask(ids, rate, seed)
        assert m.dtype == bool and m.shape == ids.shape
        scalar = np.array([is_sampled(int(i), rate, seed) for i in ids])
        assert np.array_equal(m, scalar)
        assert np.array_equal(m, sampled_mask(ids, rate, seed))
        # unbiased: the sampled fraction tracks the rate
        assert abs(m.mean() - rate) < 0.03
    assert sampled_mask(ids, 1.0, 0).all()
    assert not sampled_mask(ids, 0.0, 0).any()
    # the seed reshuffles which ids are sampled
    assert not np.array_equal(sampled_mask(ids, 0.3, 0),
                              sampled_mask(ids, 0.3, 1))


def test_core_threshold_is_rescaled_to_the_sample():
    # k_s = max(1, round(k * rate)) — DBSCAN++'s minPts rescaling — so
    # the sampled count stays an unbiased estimate of ">= k neighbors"
    for k, rate, want in [(24, 0.1, 2), (24, 1.0, 24), (256, 0.1, 26),
                          (10, 0.05, 1), (8, 0.25, 2)]:
        eng = SampledCoreDBSCAN(d=4, k=k, t=4, eps=0.5, seed=0,
                                sample_rate=rate)
        assert eng.core_k == want
    # the exact engine keeps core_k == k (the degenerate rescaling)
    from repro.core.soa import SoADynamicDBSCAN
    assert SoADynamicDBSCAN(d=4, k=24, t=4, eps=0.5, seed=0).core_k == 24


# ---------------------------------------------------------------------- #
# rate=1.0 oracle: bit-identical to the exact engine
# ---------------------------------------------------------------------- #
def test_approx_at_rate_one_is_bit_identical_to_soa():
    X, _ = blobs(n=900, d=8, n_clusters=4, cluster_std=0.3, seed=2)
    cfg = cfg8(sample_rate=1.0)
    A = build_index(cfg.replace(backend="soa"))
    B = build_index(cfg.replace(backend="approx"))
    rng = np.random.default_rng(0)
    alive = []
    for s in range(0, len(X), 150):
        assert A.insert_batch(X[s:s + 150]) == \
            (got := B.insert_batch(X[s:s + 150]))
        alive += got
        assert sorted(A.drain_deltas()) == sorted(B.drain_deltas())
        if len(alive) > 200:
            dels = [alive.pop(int(rng.integers(len(alive))))
                    for _ in range(40)]
            A.delete_batch(dels)
            B.delete_batch(dels)
            assert sorted(A.drain_deltas()) == sorted(B.drain_deltas())
        assert A.labels() == B.labels()  # identical dicts, not just ARI
    A.check_invariants()
    B.check_invariants()


def test_approx_snapshot_restore_roundtrip():
    X, _ = blobs(n=600, d=8, n_clusters=4, cluster_std=0.3, seed=4)
    ix = build_index(cfg8(backend="approx", sample_rate=0.3))
    ix.insert_batch(X[:400])
    ix.delete_batch(list(ix.ids())[::4])
    snap = ix.snapshot()
    clone = restore_index(snap)
    assert clone.labels() == ix.labels()
    ix.insert_batch(X[400:])
    clone.insert_batch(X[400:])
    assert clone.labels() == ix.labels()
    clone.check_invariants()


# ---------------------------------------------------------------------- #
# quality floors at real sampling rates
# ---------------------------------------------------------------------- #
@pytest.mark.parametrize("rate", [0.1, 0.3])
def test_approx_ari_floor_vs_exact(rate):
    X, _ = blobs(n=3000, d=8, n_clusters=4, cluster_std=0.4, seed=3)
    cfg = cfg8(k=64)  # dense buckets so k_s = round(64 * rate) >= 6
    _, exact = stream(build_index(cfg.replace(backend="soa")),
                      X, window=2000)
    _, got = stream(build_index(cfg.replace(backend="approx",
                                            sample_rate=rate)),
                    X, window=2000)
    common = sorted(set(exact) & set(got))
    ari = adjusted_rand_index([exact[i] for i in common],
                              [got[i] for i in common])
    assert ari >= 0.9, (rate, ari)


# ---------------------------------------------------------------------- #
# sharded composition
# ---------------------------------------------------------------------- #
@pytest.mark.parametrize("shards", [1, 2, 4])
def test_sharded_approx_matches_unsharded(shards):
    X, _ = blobs(n=800, d=8, n_clusters=4, cluster_std=0.3, seed=5)
    cfg = cfg8(backend="approx", sample_rate=0.3)
    ref = build_index(cfg)
    shd = build_index(cfg.with_shards(shards))
    _, want = stream(ref, X, window=500)
    _, got = stream(shd, X, window=500)
    # same sampled set (id-hash sampling is placement-independent), same
    # partition; labels may differ by anchor renaming across shards
    assert_same_partition(want, got)
    shd.close()


def test_sharded_approx_process_transport():
    X, _ = blobs(n=400, d=8, n_clusters=4, cluster_std=0.3, seed=6)
    cfg = cfg8(backend="approx", sample_rate=0.3, transport="process")
    ref = build_index(cfg8(backend="approx", sample_rate=0.3))
    shd = build_index(cfg.with_shards(2))
    try:
        _, want = stream(ref, X)
        _, got = stream(shd, X)
        assert_same_partition(want, got)
    finally:
        shd.close()


# ---------------------------------------------------------------------- #
# tiered serving index
# ---------------------------------------------------------------------- #
def test_tiered_serves_from_front_and_verifies_on_back():
    X, _ = blobs(n=1500, d=8, n_clusters=4, cluster_std=0.4, seed=8)
    cfg = cfg8(k=64, backend="tiered", sample_rate=0.2, obs=True)
    idx = build_index(cfg)
    try:
        live, served = stream(idx, X, window=1000)
        # the front tier answers immediately for every live point
        assert sorted(served) == sorted(live)
        # after the barrier the back tier has applied the whole stream
        exact = idx.exact_labels(live)
        assert sorted(exact) == sorted(live)
        common = sorted(live)
        ari = adjusted_rand_index([exact[i] for i in common],
                                  [served[i] for i in common])
        assert ari >= 0.9, ari

        # divergence is tracked in the obs snapshot (the serving-side
        # contract: dashboards read this gauge, tests pin its presence)
        snap = idx.obs.snapshot()
        m = snap["metrics"]
        assert m["tiered.divergence_ari"]["type"] == "gauge"
        assert m["tiered.divergence_ari"]["value"] >= 0.9
        assert m["tiered.lag"]["value"] == 0  # flushed by exact_labels()
        assert "tiered.queue_depth" in m and "tiered.hot_buckets" in m
        idx.check_invariants()
    finally:
        idx.close()


def test_tiered_at_rate_one_front_equals_back():
    X, _ = blobs(n=500, d=8, n_clusters=4, cluster_std=0.3, seed=9)
    idx = build_index(cfg8(backend="tiered", sample_rate=1.0))
    try:
        ids = idx.insert_batch(X)
        idx.delete_batch(ids[::5])
        live = [i for j, i in enumerate(ids) if j % 5]
        assert idx.labels(live) == idx.exact_labels(live)
    finally:
        idx.close()


def test_tiered_snapshot_restore_roundtrip():
    X, _ = blobs(n=400, d=8, n_clusters=4, cluster_std=0.3, seed=10)
    idx = build_index(cfg8(backend="tiered", sample_rate=0.3))
    try:
        idx.insert_batch(X[:300])
        snap = idx.snapshot()
        clone = restore_index(snap)
        try:
            assert clone.labels() == idx.labels()
            idx.insert_batch(X[300:])
            clone.insert_batch(X[300:])
            assert clone.labels() == idx.labels()
            assert clone.exact_labels() == idx.exact_labels()
        finally:
            clone.close()
    finally:
        idx.close()
