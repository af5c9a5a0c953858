"""Tests for the unified repro.api surface: backend registry, mixed
Insert/Delete streams, cross-backend partition equivalence, and
snapshot/restore round-trips (in memory and through CheckpointManager)."""

import numpy as np
import pytest

from repro.api import (
    NOISE,
    ClusterConfig,
    Delete,
    Insert,
    available_backends,
    build_index,
    restore_index,
)
from repro.data import blobs

DYNAMIC_BACKENDS = ("dynamic", "batched", "batched-device")
ALL_BACKENDS = available_backends()


def _bijective(la, lb) -> bool:
    for u, v in ((la, lb), (lb, la)):
        seen = {}
        for a, b in zip(u, v):
            if seen.setdefault(a, b) != b:
                return False
    return True


def assert_same_partition(A: dict, B: dict):
    """Same live ids, same noise set, same partition up to label renaming."""
    assert sorted(A) == sorted(B)
    ids = sorted(A)
    la = np.array([A[i] for i in ids])
    lb = np.array([B[i] for i in ids])
    assert np.array_equal(la == NOISE, lb == NOISE)
    mask = la != NOISE
    assert _bijective(la[mask], lb[mask])


def mixed_stream(n=400, d=4, seed=0, p_delete=0.25):
    """Deterministic mixed Insert/Delete event stream (auto-assigned ids)."""
    X, _ = blobs(n=n, d=d, n_clusters=4, cluster_std=0.15, seed=seed)
    rng = np.random.default_rng(seed)
    events, alive, nxt = [], [], 0
    for j in range(n):
        events.append(Insert(X[j]))
        alive.append(nxt)
        nxt += 1
        if rng.random() < p_delete and len(alive) > 10:
            events.append(Delete(alive.pop(int(rng.integers(len(alive))))))
    return events


# ---------------------------------------------------------------------- #
# registry / config
# ---------------------------------------------------------------------- #
def test_registry_exposes_required_backends():
    for required in ("dynamic", "batched", "batched-device", "emz-static",
                     "naive", "sharded"):
        assert required in ALL_BACKENDS


def test_register_backend_overwrite_and_unregister():
    from repro.api import register_backend, unregister_backend

    @register_backend("swap-me")
    def _a(cfg):
        return build_index(cfg.replace(backend="dynamic"))

    with pytest.raises(ValueError, match="already registered"):
        register_backend("swap-me")(_a)

    @register_backend("swap-me", overwrite=True)
    def _b(cfg):
        return build_index(cfg.replace(backend="batched"))

    index = build_index(ClusterConfig(d=2, k=2, t=2, eps=0.5,
                                      backend="swap-me"))
    from repro.core.batched import BatchedDynamicDBSCAN
    assert isinstance(index.engine, BatchedDynamicDBSCAN)
    unregister_backend("swap-me")
    assert "swap-me" not in available_backends()
    with pytest.raises(KeyError, match="swap-me"):
        unregister_backend("swap-me")


def test_unknown_backend_raises_with_listing():
    with pytest.raises(KeyError, match="dynamic"):
        build_index(ClusterConfig(d=2, k=2, t=2, eps=0.5, backend="nope"))


@pytest.mark.parametrize("bad,named", [
    (dict(d=0, k=2, t=2, eps=0.5), "d"),
    (dict(d=2, k=0, t=2, eps=0.5), "k"),
    (dict(d=2, k=2, t=0, eps=0.5), "t"),
    (dict(d=2, k=2, t=2, eps=-1.0), "eps"),
    (dict(d=2, k=2, t=2, eps=0.0), "eps"),
    (dict(d=2, k=2, t=2, eps=0.5, repair="sloppy"), "repair"),
    (dict(d=2, k=2, t=2, eps=0.5, shards=0), "shards"),
    (dict(d=2, k=2, t=2, eps=0.5, inner_backend="sharded"), "inner_backend"),
])
def test_config_validation(bad, named):
    """Bad parameters fail at construction, naming the parameter, instead
    of failing deep inside GridLSH.__init__."""
    with pytest.raises(ValueError, match=named):
        ClusterConfig(**bad)


@pytest.mark.parametrize("transport", ["process", "tcp"])
def test_device_engine_refuses_worker_process_transport(transport):
    """A wire transport spawns one process per shard; a device engine in
    each would contend for the one chip, so the config is refused."""
    base = dict(d=2, k=2, t=2, eps=0.5, backend="sharded", shards=2)
    for inner in ("soa-device", "batched-device"):
        with pytest.raises(ValueError, match="one process"):
            ClusterConfig(**base, inner_backend=inner, transport=transport)
    ClusterConfig(**base, inner_backend="soa-device", transport="local")
    ClusterConfig(**base, inner_backend="soa", transport=transport)


@pytest.mark.parametrize("backend", ALL_BACKENDS)
def test_build_index_works_for_every_backend(backend):
    X, _ = blobs(n=200, d=3, n_clusters=3, cluster_std=0.15, seed=0)
    index = build_index(ClusterConfig(d=3, k=5, t=5, eps=0.4, seed=0,
                                      backend=backend))
    ids = index.insert_batch(X)
    assert len(index) == 200 and ids[0] in index
    assert index.ids() == sorted(ids)
    lab = index.labels()
    assert set(lab) == set(ids)
    # label() agrees with labels() on cluster co-membership
    a, b = ids[0], ids[1]
    if lab[a] != NOISE and lab[b] != NOISE:
        assert (index.label(a) == index.label(b)) == (lab[a] == lab[b])
    index.check_invariants()


# ---------------------------------------------------------------------- #
# mutation semantics
# ---------------------------------------------------------------------- #
@pytest.mark.parametrize("backend", ("dynamic", "batched", "emz-static"))
def test_explicit_indices_and_duplicates(backend):
    X, _ = blobs(n=20, d=3, n_clusters=2, seed=1)
    index = build_index(ClusterConfig(d=3, k=3, t=3, eps=0.5,
                                      backend=backend))
    assert index.insert(X[0], idx=17) == 17
    with pytest.raises(KeyError):
        index.insert(X[1], idx=17)
    # auto-assignment continues past pinned ids
    assert index.insert_batch(X[1:4], ids=[None, 99, None]) == [18, 99, 100]
    with pytest.raises(KeyError):
        index.delete(12345)


@pytest.mark.parametrize("backend", ("dynamic", "batched", "emz-static",
                                     "sharded"))
def test_delete_batch_rejects_duplicate_ids(backend):
    X, _ = blobs(n=30, d=3, n_clusters=2, seed=4)
    index = build_index(ClusterConfig(d=3, k=3, t=3, eps=0.5,
                                      backend=backend))
    ids = index.insert_batch(X)
    with pytest.raises(KeyError, match=f"duplicate id {ids[7]}"):
        index.delete_batch([ids[2], ids[7], ids[7]])
    # nothing was deleted before the duplicate was detected
    assert len(index) == 30
    index.delete_batch(ids[:5])
    assert len(index) == 25


def test_engine_level_delete_batch_rejects_duplicates():
    from repro.core.batched import BatchedDynamicDBSCAN

    eng = BatchedDynamicDBSCAN(3, 3, 3, 0.5, seed=0)
    ids = eng.add_batch(np.zeros((4, 3)) + np.arange(4)[:, None])
    with pytest.raises(KeyError, match="duplicate id"):
        eng.delete_batch([ids[0], ids[0]])
    assert len(eng.points) == 4


@pytest.mark.parametrize("backend", ("dynamic", "batched"))
def test_apply_mixed_stream_returns_handles(backend):
    X, _ = blobs(n=30, d=3, n_clusters=2, seed=2)
    index = build_index(ClusterConfig(d=3, k=3, t=3, eps=0.5,
                                      backend=backend))
    out = index.apply([
        Insert(X[0]), Insert(X[1], idx=50), Delete(50),
        Insert(X[2]), Delete(0),
    ])
    assert out == [0, 50, None, 51, None]
    assert index.ids() == [51]
    index.check_invariants()


@pytest.mark.parametrize("backend", ("dynamic", "batched", "emz-static"))
def test_wrong_dimension_point_rejected(backend):
    index = build_index(ClusterConfig(d=2, k=2, t=2, eps=0.5,
                                      backend=backend))
    with pytest.raises(ValueError, match="shape"):
        index.insert(np.zeros(5))
    with pytest.raises(ValueError, match="shape"):
        index.insert_batch(np.zeros((3, 4)))


def test_apply_rejects_non_events():
    index = build_index(ClusterConfig(d=2, k=2, t=2, eps=0.5))
    with pytest.raises(TypeError):
        index.apply([("add", [0.0, 0.0])])


# ---------------------------------------------------------------------- #
# cross-backend equivalence
# ---------------------------------------------------------------------- #
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_insert_stream_equivalent_across_backends(seed):
    """Same insert stream ⇒ same partition (up to label permutation)
    across the dynamic engines and both recompute baselines."""
    X, _ = blobs(n=350, d=4, n_clusters=4, cluster_std=0.15, seed=seed)
    cfg = ClusterConfig(d=4, k=8, t=8, eps=0.45, seed=seed)
    ref = None
    for backend in ("dynamic", "batched", "emz-static", "naive"):
        index = build_index(cfg.replace(backend=backend))
        index.insert_batch(X)
        lab = index.labels()
        if ref is None:
            ref = lab
        else:
            assert_same_partition(ref, lab)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_mixed_stream_equivalent_across_backends(seed):
    """Same mixed Insert/Delete stream ⇒ same partition across
    "dynamic"/"batched"/"naive" (ISSUE acceptance) + "emz-static"."""
    events = mixed_stream(n=400, d=4, seed=seed)
    ref = None
    cfg = ClusterConfig(d=4, k=8, t=8, eps=0.45, seed=seed)
    for backend in ("dynamic", "batched", "naive", "emz-static"):
        index = build_index(cfg.replace(backend=backend))
        index.apply(events)
        index.check_invariants()
        lab = index.labels()
        if ref is None:
            ref = lab
        else:
            assert_same_partition(ref, lab)


# ---------------------------------------------------------------------- #
# snapshot / restore
# ---------------------------------------------------------------------- #
@pytest.mark.parametrize("backend", ("dynamic", "batched", "emz-static",
                                     "naive"))
def test_snapshot_restore_roundtrip_1k_updates(backend):
    """Acceptance criterion: snapshot()/restore() preserves
    check_invariants() and cluster labels on a 1k-update workload."""
    events = mixed_stream(n=800, d=4, seed=3, p_delete=0.3)
    assert len(events) > 1000
    index = build_index(ClusterConfig(d=4, k=8, t=8, eps=0.45, seed=3,
                                      backend=backend))
    index.apply(events)
    restored = restore_index(index.snapshot())
    restored.check_invariants()
    assert restored.labels() == index.labels()
    assert restored.ids() == index.ids()
    # restored index stays live: new updates land on fresh handles
    new = restored.insert(np.zeros(4))
    assert new not in index
    restored.delete(new)
    assert restored.labels() == index.labels()


def test_snapshot_restore_preserves_exact_forest():
    """The dynamic snapshot stores the spanning forest explicitly, so the
    restored structure matches edge-for-edge (not just up to partition)."""
    events = mixed_stream(n=300, d=3, seed=5)
    index = build_index(ClusterConfig(d=3, k=6, t=6, eps=0.5, seed=5))
    index.apply(events)
    restored = restore_index(index.snapshot())
    assert (sorted(index.engine.forest._edge)
            == sorted(restored.engine.forest._edge))
    assert index.engine.support == restored.engine.support
    assert index.engine.attach == restored.engine.attach


def test_restore_refuses_config_mismatch_and_non_empty():
    index = build_index(ClusterConfig(d=3, k=4, t=4, eps=0.5))
    index.insert(np.zeros(3))
    snap = index.snapshot()
    other = build_index(ClusterConfig(d=3, k=5, t=4, eps=0.5))
    with pytest.raises(ValueError, match="config"):
        other.restore(snap)
    with pytest.raises(ValueError, match="empty"):
        index.restore(snap)


def test_checkpoint_manager_index_roundtrip(tmp_path):
    from repro.checkpoint.manager import CheckpointManager

    events = mixed_stream(n=300, d=4, seed=7)
    index = build_index(ClusterConfig(d=4, k=6, t=6, eps=0.5, seed=7,
                                      backend="batched"))
    index.apply(events)
    mgr = CheckpointManager(tmp_path, async_write=False)
    mgr.save_index(3, index)
    mgr.save_index(8, index)
    assert mgr.latest_index_step() == 8
    restored = mgr.restore_index()
    restored.check_invariants()
    assert restored.labels() == index.labels()
    assert restored.cfg == index.cfg


# ---------------------------------------------------------------------- #
# satellite regressions
# ---------------------------------------------------------------------- #
def test_labels_identical_without_scipy(monkeypatch):
    """DynamicDBSCAN.labels must work (and agree) without scipy: the
    pure-Python union-find fallback produces the identical labelling."""
    import repro.core.dynamic_dbscan as dd

    events = mixed_stream(n=250, d=3, seed=9)
    index = build_index(ClusterConfig(d=3, k=6, t=6, eps=0.5, seed=9))
    index.apply(events)
    with_scipy = index.labels()

    monkeypatch.setattr(dd, "_sp", None)  # as if scipy were uninstalled
    assert index.labels() == with_scipy


def test_emz_fixed_is_insert_only():
    index = build_index(ClusterConfig(d=3, k=4, t=4, eps=0.5,
                                      backend="emz-fixed"))
    X, _ = blobs(n=120, d=3, n_clusters=3, cluster_std=0.15, seed=0)
    ids = index.insert_batch(X[:100])
    index.insert_batch(X[100:])
    assert len(index.labels()) == 120
    with pytest.raises(NotImplementedError):
        index.delete(ids[0])


def test_emz_fixed_incremental_matches_engine_and_restores():
    """The adapter feeds EMZFixedCore incrementally (no per-query rebuild)
    and pinned out-of-order handles name stream positions, not positions
    in the frozen first batch."""
    from repro.core import EMZFixedCore

    X, _ = blobs(n=150, d=3, n_clusters=3, cluster_std=0.15, seed=1)
    cfg = ClusterConfig(d=3, k=4, t=4, eps=0.5, seed=1, backend="emz-fixed")
    index = build_index(cfg)
    ids = index.insert_batch(X[:100])
    # pinned handle below every auto id: must NOT join the frozen batch
    ids += index.apply([Insert(x, idx=i - 1000)
                        for i, x in enumerate(X[100:])])
    eng = EMZFixedCore(3, 4, 4, 0.5, seed=1)
    eng.add_batch(X[:100])
    expected = eng.add_batch(X[100:])
    assert [index.labels()[i] for i in ids] == [int(v) for v in expected]
    restored = restore_index(index.snapshot())
    assert restored.labels() == index.labels()
