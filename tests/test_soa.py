"""Tests for the vectorised structure-of-arrays engine (backend="soa"):
kernel bit-exactness for the bucket/core ops, seeded oracle equivalence
against the sequential dict engines on mixed insert/delete/label streams
(including snapshot/restore round-trips), and inner_backend="soa" under
ShardedIndex at S in {1, 2, 4}."""

import numpy as np
import pytest

from repro.api import (
    NOISE,
    ClusterConfig,
    build_index,
    restore_index,
)
from repro.data import blobs

from test_api import assert_same_partition, mixed_stream


def cfg4(**kw):
    base = dict(d=4, k=8, t=8, eps=0.45, seed=0)
    base.update(kw)
    return ClusterConfig(**base)


# ---------------------------------------------------------------------- #
# device programs (the platform-chosen path in ops) vs numpy
# ---------------------------------------------------------------------- #
@pytest.mark.parametrize("n,t,nb", [(1, 1, 1), (7, 3, 5), (203, 7, 37),
                                    (256, 8, 128), (301, 10, 513)])
def test_bucket_core_stats_matches_ref(n, t, nb):
    import jax.numpy as jnp

    from repro.kernels import ops

    rng = np.random.default_rng(n * 31 + t)
    slots = rng.integers(0, nb, (n, t)).astype(np.int32)
    sizes = rng.integers(0, 12, nb).astype(np.int32)
    for k in (1, 3, 9):
        sp, cp = ops.bucket_core_stats(jnp.asarray(slots),
                                       jnp.asarray(sizes), k=k)
        want = (sizes[slots] >= k).sum(axis=1).astype(np.int32)
        assert np.array_equal(np.asarray(sp), want)
        assert np.array_equal(np.asarray(cp), (want > 0).astype(np.int32))


@pytest.mark.parametrize("n,t,nb", [(1, 1, 1), (7, 3, 5), (203, 7, 37),
                                    (256, 8, 128), (301, 10, 513)])
def test_slot_counts_matches_bincount(n, t, nb):
    import jax.numpy as jnp

    from repro.kernels import ops

    rng = np.random.default_rng(n * 17 + nb)
    slots = rng.integers(0, nb, (n, t)).astype(np.int32)
    want = np.bincount(slots.ravel(), minlength=nb).astype(np.int32)
    got = np.asarray(ops.slot_counts(jnp.asarray(slots), n_slots=nb))
    assert np.array_equal(got, want)
    # the engine's padded form: a larger capacity only appends zeros, and
    # rows of the out-of-range id (the capacity itself) drop out
    padded = np.concatenate([slots, np.full((5, t), 2 * nb, np.int32)])
    got = np.asarray(ops.slot_counts(jnp.asarray(padded), n_slots=2 * nb))
    assert np.array_equal(got[:nb], want) and not got[nb:].any()


def _host_least_rows(slots, core):
    """Least core row of each core row's component, -1 elsewhere: the host
    SV over chain edges, as the soa engine computes its epoch."""
    from repro.core.soa import _sv_components

    n, t = slots.shape
    rows = np.nonzero(core)[0]
    flat = slots[rows].ravel()
    order = np.argsort(flat, kind="stable")
    sf, rf = flat[order], np.repeat(rows, t)[order]
    same = sf[1:] == sf[:-1]
    parent, _ = _sv_components(n, rf[:-1][same], rf[1:][same])
    return np.where(core, parent, -1)


@pytest.mark.parametrize("block", [1, 7, 64, 4096])
@pytest.mark.parametrize("shape", ["random", "chain"])
def test_core_components_matches_host_sv(shape, block):
    """The device epoch program against the host SV on graphs of random
    and path shape, with blocks small enough that the first round, the
    check and the later rounds each cross several blocks, rows past the
    last core row, and packed leftovers."""
    import jax

    from repro.kernels import ref

    rng = np.random.default_rng(block)
    program = jax.jit(ref.core_components, static_argnums=(2, 3))
    for t in range(1, 5):
        n = int(rng.integers(2, 300))
        if shape == "random":
            # slot ids distinct across tables, as the directory gives them
            slots = rng.integers(0, 40, (n, t)) * t + np.arange(t)
        else:
            # row j joins slots j and j+1 of a scrambled path
            path = rng.permutation(n + 1)
            slots = np.stack([path[:-1], path[1:]] * t, axis=1)[:, :t]
        slots = slots.astype(np.int32)
        core = rng.random(n) < 0.8
        core[int(rng.integers(n)):] &= t % 2 == 0  # a dead tail
        n_slots = int(slots.max()) + 1 + t
        least, rounds = program(slots.reshape(-1), core, n_slots, block)
        got = np.where(core, np.asarray(least)[slots[:, 0]], -1)
        assert np.array_equal(got, _host_least_rows(slots, core))
        assert (int(rounds) > 0) == bool(core.any())


# ---------------------------------------------------------------------- #
# oracle equivalence: soa vs the sequential dict engines
# ---------------------------------------------------------------------- #
@pytest.mark.parametrize("backend", ["soa", "soa-device"])
def test_soa_registered_and_event_stream_matches_dynamic(backend):
    cfg = cfg4()
    ref = build_index(cfg.replace(backend="dynamic"))
    soa = build_index(cfg.replace(backend=backend))
    for ev in mixed_stream(n=250, seed=3):
        assert ref.apply([ev]) == soa.apply([ev])
    assert ref.labels() == soa.labels()
    assert sorted(ref.ids()) == sorted(soa.ids())
    soa.check_invariants()


@pytest.mark.parametrize("seed", range(4))
@pytest.mark.parametrize("orphans", [True, False])
def test_soa_batches_match_batched_labels_exactly(seed, orphans):
    """Batch-grained mixed stream with pinned out-of-order ids: identical
    label dicts (not just same partition) and identical compacted journal
    deltas at every step."""
    rng = np.random.default_rng(seed + 50)
    X, _ = blobs(n=400, d=4, n_clusters=4, cluster_std=0.3, seed=seed)
    cfg = cfg4(seed=seed, attach_orphans=orphans)
    A = build_index(cfg.replace(backend="batched"))
    B = build_index(cfg.replace(backend="soa"))
    pos, alive = 0, []
    while pos < len(X):
        b = int(rng.integers(1, 50))
        chunk = X[pos:pos + b]
        pos += b
        ids = None
        if rng.random() < 0.3:
            base = 10_000 + pos * 10
            ids = [None if rng.random() < 0.5 else base + j
                   for j in range(len(chunk))]
        assert A.insert_batch(chunk, ids=ids) == \
            (got := B.insert_batch(chunk, ids=ids))
        alive.extend(got)
        assert sorted(A.drain_deltas()) == sorted(B.drain_deltas())
        if rng.random() < 0.5 and len(alive) > 30:
            nd = int(rng.integers(1, min(20, len(alive) - 10)))
            dels = [alive.pop(int(rng.integers(len(alive))))
                    for _ in range(nd)]
            A.delete_batch(dels)
            B.delete_batch(dels)
            assert sorted(A.drain_deltas()) == sorted(B.drain_deltas())
        assert A.labels() == B.labels()
    A.check_invariants()
    B.check_invariants()


def test_soa_point_queries_agree_with_bulk_labels():
    cfg = cfg4(seed=1)
    ix = build_index(cfg.replace(backend="soa"))
    X, _ = blobs(n=300, d=4, n_clusters=3, cluster_std=0.3, seed=1)
    ids = ix.insert_batch(X)
    labs = ix.labels()
    for i in ids[::7]:
        assert ix.label(i) == ix.component_of(i) == labs[i]
        if ix.is_core(i):
            assert ix.core_anchor_of(i) == i


def test_soa_snapshot_restore_roundtrip_mid_stream():
    cfg = cfg4(seed=2)
    ix = build_index(cfg.replace(backend="soa"))
    X, _ = blobs(n=350, d=4, n_clusters=4, cluster_std=0.3, seed=2)
    ix.insert_batch(X[:200])
    ix.delete_batch(list(ix.ids())[::5])
    rest = restore_index(ix.snapshot())
    assert rest.labels() == ix.labels()
    assert rest.ids() == ix.ids()
    rest.check_invariants()
    # the restored index keeps tracking the original under further updates
    a = ix.insert_batch(X[200:])
    b = rest.insert_batch(X[200:])
    assert a == b
    assert rest.labels() == ix.labels()


def test_soa_rejects_duplicate_ids_atomically():
    ix = build_index(cfg4().replace(backend="soa"))
    X, _ = blobs(n=10, d=4, n_clusters=1, cluster_std=0.2, seed=0)
    ix.insert_batch(X[:3], ids=[7, 8, 9])
    with pytest.raises(KeyError):
        ix.insert_batch(X[3:6], ids=[11, 8, 12])
    # the failed batch must not have committed any of its rows
    assert sorted(ix.ids()) == [7, 8, 9]


# ---------------------------------------------------------------------- #
# sharded composition: inner_backend="soa"
# ---------------------------------------------------------------------- #
@pytest.mark.parametrize("shards", [1, 2, 4])
def test_sharded_soa_matches_inner_dynamic(shards):
    base = dict(d=4, k=8, t=8, eps=0.45, seed=0)
    ref = build_index(ClusterConfig(backend="dynamic", **base))
    sh = build_index(ClusterConfig(backend="sharded", shards=shards,
                                   inner_backend="soa", **base))
    for ev in mixed_stream(n=220, seed=5):
        assert ref.apply([ev]) == sh.apply([ev])
    assert_same_partition(ref.labels(), sh.labels())
    sh.check_invariants()


def test_sharded_soa_snapshot_roundtrip():
    cfg = ClusterConfig(backend="sharded", shards=2, inner_backend="soa",
                        d=4, k=8, t=8, eps=0.45, seed=0)
    sh = build_index(cfg)
    X, _ = blobs(n=240, d=4, n_clusters=3, cluster_std=0.3, seed=4)
    sh.insert_batch(X)
    sh.delete_batch(list(sh.ids())[::4])
    rest = restore_index(sh.snapshot())
    assert rest.labels() == sh.labels()
    assert rest.ids() == sh.ids()


# ---------------------------------------------------------------------- #
# the connectivity epoch on the device (soa-device) vs the host SV (soa)
# ---------------------------------------------------------------------- #
EPS, K, T = 0.5, 3, 4


def _chain(rng, n_clumps, x0=0.0):
    """K copies of a point every EPS along a line: consecutive clumps
    share a bucket in some tables, clumps two apart in none, so the core
    set is one path of buckets.  Clumps arrive in random order, so slot
    ids are scrambled along the path."""
    pos = rng.permutation(n_clumps)
    X = np.zeros((n_clumps * K, 2))
    X[:, 0] = x0 + np.repeat(pos * EPS, K)
    return X, np.repeat(pos, K)


def _epoch_script(case, rng):
    """(op, arg) steps; every mutation is followed by an epoch check."""
    if case == "split":
        X, pos = _chain(rng, 24)
        ext, _ = _chain(rng, 6, x0=40.0)
        # the middle clump goes, then a point of each remaining clump
        return [("insert", X), ("insert", ext),
                ("delete", np.nonzero(pos == 12)[0]),
                ("delete", np.nonzero(np.diff(pos, prepend=-1))[0][::3])]
    if case == "chain":
        X, _ = _chain(rng, 64)
        return [("insert", X[:K * 40]), ("insert", X[K * 40:])]
    if case == "no_core":
        far = np.zeros((20, 2))
        far[:, 1] = np.arange(20) * 10 * EPS  # one point a bucket
        X, _ = _chain(rng, 3, x0=-50.0)
        return [("insert", far), ("insert", X),
                ("delete", np.arange(20, 20 + len(X)))]
    if case == "doubling":
        # the first epoch at row capacity 256 and slot capacity 256, the
        # second after both have doubled
        X, _ = _chain(rng, 200)
        return [("insert", X[:60]), ("insert", X[60:])]
    raise ValueError(case)


@pytest.mark.parametrize("orphans", [True, False])
@pytest.mark.parametrize("case", ["split", "chain", "no_core", "doubling"])
def test_device_epoch_matches_host_epoch(case, orphans):
    """soa-device computes the epoch on the device over the bucket graph,
    soa with the host SV over chain edges: the same handles, labels() and
    drained deltas after every epoch."""
    rng = np.random.default_rng(7)
    cfg = ClusterConfig(d=2, k=K, t=T, eps=EPS, seed=1,
                        attach_orphans=orphans, obs=True)
    host = build_index(cfg.replace(backend="soa"))
    dev = build_index(cfg.replace(backend="soa-device"))
    host.drain_deltas(), dev.drain_deltas()
    caps = []
    ids: list = []
    for op, arg in _epoch_script(case, rng):
        if op == "insert":
            got = host.insert_batch(arg)
            assert dev.insert_batch(arg) == got
            ids += got
        else:
            dels = [ids[int(j)] for j in arg]
            host.delete_batch(dels)
            dev.delete_batch(dels)
            ids = [i for i in ids if i not in set(dels)]
        assert [dev.label(i) for i in ids] == [host.label(i) for i in ids]
        assert dev.labels() == host.labels()
        assert dev.drain_deltas() == host.drain_deltas()
        caps.append((dev.engine._cap, len(dev.engine._bsize)))
    # an epoch after each step: rebuilt after a delete or a row-capacity
    # growth, merged after an insert into a cached epoch
    for ix in (host, dev):
        assert (ix.engine.n_epoch_rebuilds + ix.engine.n_epoch_merges
                == len(caps))
    rounds = [s["args"]["rounds"] for s in dev.obs.tracer.export()
              if s["name"] == "soa.rebuild"]
    assert all(s["args"]["on_device"] for s in dev.obs.tracer.export()
               if s["name"] == "soa.rebuild")
    # the device program over the final state gives the epoch's rows,
    # whether the last step rebuilt or merged it
    full, _, _, last = dev.engine._device_comp()
    assert np.array_equal(full, dev.engine._ensure_comp())
    rounds.append(last)
    labels = dev.labels()
    if case == "split":
        # two chains, then the first cut in two
        assert len(set(labels.values()) - {NOISE}) == 3
    elif case == "chain":
        assert len(set(labels.values())) == 1 and max(rounds) >= 4
    elif case == "no_core":
        assert rounds[-1] == 0 and set(labels.values()) == {NOISE}
    else:
        assert caps[0] == (256, 256) and caps[1][0] > 256 < caps[1][1]


# ---------------------------------------------------------------------- #
# the epoch kept across inserts: merged, equal to a full rebuild
# ---------------------------------------------------------------------- #
def _epoch_handles(ix):
    """Handles by row (-1 off the core rows) of the epoch that ``label()``
    reads, merged if inserts came since it was computed, and of a full
    rebuild of the same state on the engine's own path."""
    eng = ix.engine
    got = eng._ensure_comp()
    full = (eng._device_comp if eng.use_device else eng._host_comp)()[0]
    return [np.where(c >= 0, eng._ids[c], -1) for c in (got, full)]


def _clumps(pos):
    """K points at each position times EPS along the x axis (``_chain``'s
    clumps, in the order given)."""
    X = np.zeros((len(pos) * K, 2))
    X[:, 0] = np.repeat(np.asarray(pos, float) * EPS, K)
    return X


def _core_handles(ix, ids):
    return {ix.label(i) for i in ids if ix.is_core(i)}


# the stream's steps: insert the next batch, read, expire the oldest
# batch, restore from a snapshot
MERGE_STREAM = "I I R I R I I R I R E R I R S R I I R E I R I R"


def _merge_stream(ix, rng):
    """A sliding window over sparse, noisy batches: up to two inserts
    between reads, expiries, row-capacity growth, and a restore from a
    snapshot.  Yields the index at each read, with whether the rows it
    merges hold one that was live before its batch (a promotion)."""
    X = _mixture(150 * MERGE_STREAM.count("I"), SPARSE["d"],
                 seed=int(rng.integers(1 << 30)))
    alive: list = []
    lo, before = 0, np.zeros(0, np.int64)
    for op in MERGE_STREAM.split():
        if op == "I":
            before = np.asarray(alive, np.int64)
            alive += ix.insert_batch(X[lo:lo + 150])
            lo += 150
        elif op == "E":
            ix.delete_batch(alive[:150])
            alive = alive[150:]
        elif op == "S":
            ix = restore_index(ix.snapshot())
        else:
            eng = ix.engine
            pend = (np.concatenate(eng._pending) if eng._pending
                    else np.zeros(0, np.int64))
            yield ix, bool(np.isin(eng._ids[pend], before).any())


@pytest.mark.parametrize("orphans", [True, False])
@pytest.mark.parametrize("case", ["stream", "bridge", "recycled"])
@pytest.mark.parametrize("backend", ["soa", "soa-device", "approx"])
def test_merged_epoch_matches_full_rebuild(backend, case, orphans):
    """After inserts into a cached epoch, the first read merges the new
    core rows into it; its handles, by row, equal a full rebuild's.
    ``stream``: noisy batches whose crossings promote existing rows, two
    inserts between some reads, expiries, capacity growth and a restore;
    ``bridge``: one clump joins two chains; ``recycled``: a clump lands in
    rows freed below a chain's least row, so the chain's handle moves."""
    if case == "stream":
        cfg = ClusterConfig(**SPARSE, backend=backend, attach_orphans=orphans,
                            sample_rate=0.5 if backend == "approx" else 1.0)
        merges = promoted = rebuilds = 0
        for ix, old_row_merged in _merge_stream(build_index(cfg),
                                                np.random.default_rng(3)):
            merged = ix.engine.n_epoch_merges
            rebuilt = ix.engine.n_epoch_rebuilds
            got, full = _epoch_handles(ix)
            assert np.array_equal(got, full)
            merges += ix.engine.n_epoch_merges - merged
            rebuilds += ix.engine.n_epoch_rebuilds - rebuilt
            promoted += old_row_merged
            ix.check_invariants()
        assert merges >= 3 and rebuilds >= 3 and promoted >= 1
        return
    ix = build_index(ClusterConfig(d=2, k=K, t=T, eps=EPS, seed=1,
                                   backend=backend, attach_orphans=orphans))
    if case == "bridge":
        left = ix.insert_batch(_clumps(range(10)))
        assert len(_core_handles(ix, left)) == 1
        # a second chain, all in slots that held no core row: a merge
        right = ix.insert_batch(_clumps(range(11, 21)))
        assert len(_core_handles(ix, left + right)) == 2
        got, full = _epoch_handles(ix)
        assert np.array_equal(got, full)
        # and the clump between them, which the second merge finds in
        # slots the first one filled
        ix.insert_batch(_clumps([10]))
        merges = 2
    else:
        far = np.zeros((20, 2))
        far[:, 1] = np.arange(1, 21) * 10 * EPS  # one point a bucket
        noise = ix.insert_batch(far)
        chain = ix.insert_batch(_clumps(range(10)))
        (old,) = _core_handles(ix, chain)
        ix.delete_batch(noise)
        assert _core_handles(ix, chain) == {old}
        added = ix.insert_batch(_clumps([10]))  # rows from the freed ones
        merges = 1
    assert ix.engine._pending
    got, full = _epoch_handles(ix)
    assert np.array_equal(got, full)
    assert ix.engine.n_epoch_merges == merges
    handles = _core_handles(ix, ix.ids())
    assert len(handles) == 1
    if case == "recycled":
        # the least row is now a recycled one, below the chain's old least
        (new,) = handles
        assert new in added and ix.engine._row[new] < ix.engine._row[old]
    ix.check_invariants()


# ---------------------------------------------------------------------- #
# sparse, noise-heavy data: the grab and scan replays, and per-batch work
# sized by the batch
# ---------------------------------------------------------------------- #
def _mixture(n, d, seed, noise=0.1):
    """Zipf-weighted Gaussian clusters of differing spread with a share of
    uniform background noise, standardised: buckets of every size, most
    of them sparse, and cores, borders and noise side by side."""
    rng = np.random.default_rng([seed, 1])
    model = np.random.default_rng(0)
    centers = model.uniform(-3.5, 3.5, (5, d))
    w = 1.0 / np.arange(1, 6)
    stds = model.uniform(0.15, 0.5, 5)
    lab = rng.choice(5, size=n, p=w / w.sum())
    X = centers[lab] + rng.normal(size=(n, d)) * stds[lab, None]
    rows = rng.random(n) < noise
    X[rows] = rng.uniform(-4.5, 4.5, (int(rows.sum()), d))
    return (X - X.mean(0)) / X.std(0)


SPARSE = dict(d=6, k=10, t=10, eps=0.3, seed=0)


@pytest.mark.parametrize("backend", ["soa", "soa-device", "approx"])
def test_sparse_noisy_batches_match_dynamic(backend):
    """A sliding window over sparse, noisy data, where the grab and scan
    replays run on every insert: after every insert and expire batch the
    labels, every point's anchor and the drained deltas are dynamic's."""
    X = _mixture(2600, SPARSE["d"], seed=3)
    cfg = ClusterConfig(**SPARSE, sample_rate=1.0)
    ref = build_index(cfg.replace(backend="dynamic"))
    ix = build_index(cfg.replace(backend=backend))
    ref.drain_deltas(), ix.drain_deltas()
    alive: list = []
    for lo in range(0, len(X), 200):
        got = ix.insert_batch(X[lo:lo + 200])
        assert got == ref.insert_batch(X[lo:lo + 200])
        alive += got
        steps = [ix.drain_deltas]
        if len(alive) > 1000:
            dels, alive = alive[:200], alive[200:]
            steps.append(lambda: (ix.delete_batch(dels), ref.delete_batch(
                dels), ix.drain_deltas())[-1])
        for step in steps:
            deltas = step()
            assert sorted(deltas) == sorted(ref.drain_deltas())
            assert ix.labels() == ref.labels()
            assert ([ix.core_anchor_of(i) for i in alive]
                    == [ref.core_anchor_of(i) for i in alive])
    ix.check_invariants()
    assert ix.engine.n_grab_events > 0 and ix.engine.n_scan_events > 0


@pytest.mark.parametrize("backend", ["soa", "soa-device", "approx"])
def test_batch_allocates_less_than_one_directory_array(backend):
    """With a directory of 2^20 slots, one 1,000-point insert and one
    expiry each allocate less than a single int32 array of the directory's
    size: the batch's work is over its own rows, slots and members.  The
    window is a fixed set of hot spots beside sparse noise that holds
    most of the slots; the batches fall on the hot spots, so what they
    add to the directory (a member set per new bucket) stays small."""
    import tracemalloc

    rng = np.random.default_rng(0)
    centers = rng.uniform(-40, 40, (5, 4))

    def hot(n):
        return centers[rng.integers(0, 5, n)] + rng.normal(0, 0.08, (n, 4))

    ix = build_index(ClusterConfig(backend=backend, d=4, k=3, t=10,
                                   eps=0.05, seed=0))
    ids = []
    for _ in range(3):
        ids += ix.insert_batch(hot(1000))
    for _ in range(52):  # one bucket per point and table
        ids += ix.insert_batch(rng.uniform(-50, 50, (1000, 4)))
    eng = ix.engine
    assert len(eng._bsize) == 1 << 20 and eng._n_slots > 1 << 19
    directory = 4 * len(eng._bsize)
    X = hot(1000)
    grabs, scans = eng.n_grab_events, eng.n_scan_events
    tracemalloc.start()
    try:
        base = tracemalloc.get_traced_memory()[0]
        ids += ix.insert_batch(X)
        add_peak = tracemalloc.get_traced_memory()[1] - base
        tracemalloc.reset_peak()
        base = tracemalloc.get_traced_memory()[0]
        ix.delete_batch(ids[:1000])
        del_peak = tracemalloc.get_traced_memory()[1] - base
    finally:
        tracemalloc.stop()
    assert add_peak < directory and del_peak < directory
    assert eng.n_grab_events > grabs and eng.n_scan_events > scans


def test_replay_rows_do_not_grow_with_the_window():
    """The grab and scan replays examine the members of the batch's own
    buckets, so the same batch into a window ten times larger examines
    about as many rows."""
    batch = _mixture(1000, SPARSE["d"], seed=99)
    rows = []
    for window in (2000, 20000):
        ix = build_index(ClusterConfig(backend="soa", obs=True, **SPARSE))
        ix.insert_batch(_mixture(window, SPARSE["d"], seed=window))
        before = ix.obs.snapshot()["metrics"]
        ix.insert_batch(batch)
        after = ix.obs.snapshot()["metrics"]
        rows.append(sum(after[n]["value"] - before[n]["value"]
                        for n in ("soa.replay.grab_rows",
                                  "soa.replay.scan_rows")))
    assert min(rows) > 0 and max(rows) < 2 * min(rows), rows
