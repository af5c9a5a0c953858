"""Engine spans and counters of the vectorised engine (``core/soa.py``):
the phase span tree under ``insert_batch`` / ``delete_batch`` / the first
``label()`` after a mutation, the host<->device byte counters against a
hand count from the padded shapes, the Shiloach–Vishkin round counter,
and that tracing changes no answer and costs nothing when off."""

import numpy as np
import pytest

from repro.api import ClusterConfig, build_index
from repro.core.soa import SoADynamicDBSCAN
from repro.data import blobs
from repro.obs.trace import Tracer

D, T = 4, 8


def cfg(backend="soa-device", obs=True, **kw):
    base = dict(d=D, k=8, t=T, eps=0.45, seed=0, backend=backend, obs=obs)
    base.update(kw)
    return ClusterConfig(**base)


def points(n=300, seed=0):
    return blobs(n=n, d=D, n_clusters=3, cluster_std=0.3, seed=seed)[0]


def tree(spans):
    """(name, parent name) of every recorded span."""
    by_id = {s["span"]: s["name"] for s in spans}
    return [(s["name"], by_id.get(s["parent"])) for s in spans]


INSERT = {("soa.insert", None)} | {
    (f"soa.insert.{p}", "soa.insert")
    for p in ("claim", "hash", "resolve_slots", "batch_stats", "crossings",
              "members", "commit", "events")} | {
    ("soa.journal.compact", "soa.insert"),
    ("soa.device.fetch", "soa.insert.hash"),
    ("soa.device.fetch", "soa.insert.batch_stats")}
EXPIRE = {("soa.expire", None), ("soa.journal.compact", "soa.expire"),
          ("soa.epoch.drop", "soa.expire")} | {
    (f"soa.expire.{p}", "soa.expire")
    for p in ("departures", "replay", "relink")}
REBUILD = {("soa.rebuild", None), ("soa.rebuild.edges", "soa.rebuild"),
           ("soa.rebuild.sv", "soa.rebuild"),
           ("soa.device.fetch", "soa.rebuild.sv")}


# the rows the insert's event replay examined: grab and scan candidates
REPLAY = ("soa.replay.grab_rows", "soa.replay.scan_rows")


def test_insert_expire_label_span_tree():
    ix = build_index(cfg())
    ix.drain_deltas()  # start the change feed, so the journal compacts
    ids = ix.insert_batch(points())
    spans = ix.obs.tracer.drain_export()
    got = tree(spans)
    assert set(got) == INSERT
    (root,) = [s for s in spans if s["name"] == "soa.insert"]
    assert root["args"] == {"n": 300}
    # one fetch after the hash, two (counts, support) in batch_stats
    assert got.count(("soa.device.fetch", "soa.insert.hash")) == 1
    assert got.count(("soa.device.fetch", "soa.insert.batch_stats")) == 2
    ix.delete_batch(ids[:50])
    assert set(tree(ix.obs.tracer.drain_export())) == EXPIRE
    core = next(i for i in ids[50:] if ix.is_core(i))
    ix.label(core)
    ix.label(core)  # the cached epoch answers without a rebuild span
    assert sorted(tree(ix.obs.tracer.drain_export())) == sorted(REBUILD)


def test_single_point_expire_has_a_span():
    ix = build_index(cfg())
    ids = ix.insert_batch(points(60))
    ix.obs.tracer.clear()
    ix.delete_batch(ids[:1])  # the sequential path for tiny batches
    assert tree(ix.obs.tracer.export()) == [("soa.epoch.drop", "soa.expire"),
                                            ("soa.expire", None)]
    assert ix.obs.tracer.export()[1]["args"] == {"n": 1}


def test_copy_bytes_match_the_padded_shapes():
    ix = build_index(cfg())
    X = points(300)
    ix.insert_batch(X)
    # the batch pads to 512 rows; the slot vector doubles from 256 until
    # it holds every slot the batch could open (0 + 300 * t = 2,400)
    rows, cap = 512, 4096
    assert len(ix.engine._bsize) == cap
    # the occupancy programs run in the batch's local slot space: as many
    # slots as the batch has entries (300 * t = 2,400), to a power of two
    local = 4096
    h2d = (rows * D * 4          # points, f32
           + T * 4               # eta, f32
           + 2 * T * D * 4       # mixers, int32
           + rows * T * 4        # local slot ids, int32
           + local * 4)          # touched slots' sizes, int32
    d2h = (rows * T * 2 * 4      # keys, two int32 words
           + local * 4           # occupancy delta, int32
           + rows * 4)           # support, int32
    m = ix.obs.snapshot()["metrics"]
    assert m["soa.h2d_bytes"]["value"] == h2d
    assert m["soa.d2h_bytes"]["value"] == d2h
    # expiry copies nothing; the rebuild ships the whole slot matrix and
    # the core mask at the row capacity, and fetches one least core row
    # per slot and the round count
    ix.delete_batch(list(ix.ids())[:40])
    m2 = ix.obs.snapshot()["metrics"]
    assert m2["soa.h2d_bytes"] == m["soa.h2d_bytes"]
    assert m2["soa.d2h_bytes"] == m["soa.d2h_bytes"]
    ix.labels()
    assert ix.engine._cap == rows
    m3 = ix.obs.snapshot()["metrics"]
    assert m3["soa.h2d_bytes"]["value"] == h2d + (
        rows * T * 4              # slots, int32
        + rows)                   # core mask, bool
    assert m3["soa.d2h_bytes"]["value"] == d2h + (
        cap * 4                   # least core row per slot, int32
        + 4)                      # rounds, int32


def test_host_engine_counts_no_copies():
    ix = build_index(cfg(backend="soa"))
    ix.insert_batch(points())
    names = {s["name"] for s in ix.obs.tracer.export()}
    assert "soa.device.fetch" not in names and "soa.insert.hash" in names
    assert "soa.h2d_bytes" not in ix.obs.snapshot()["metrics"]


def test_rebuild_counts_sv_rounds_and_edges():
    ix = build_index(cfg())
    ids = ix.insert_batch(points())
    ix.labels()
    ix.delete_batch(ids[:30])
    ix.labels()
    rebuilds = [s for s in ix.obs.tracer.export()
                if s["name"] == "soa.rebuild"]
    assert len(rebuilds) == ix.engine.n_epoch_rebuilds == 2
    rounds = [s["args"]["rounds"] for s in rebuilds]
    assert all(r >= 1 for r in rounds)
    assert all(s["args"]["edges"] > 0 for s in rebuilds)
    assert all(s["args"]["on_device"] for s in rebuilds)
    m = ix.obs.snapshot()["metrics"]
    assert m["soa.sv_rounds"]["value"] == sum(rounds)
    # every epoch of soa-device is computed on the device
    assert m["soa.rebuild.device"]["value"] == ix.engine.n_epoch_rebuilds
    assert set(m) == {"soa.h2d_bytes", "soa.d2h_bytes", "soa.sv_rounds",
                      "soa.rebuild.device"} | set(REPLAY)


def test_host_engine_rebuilds_on_the_host():
    ix = build_index(cfg(backend="soa"))
    ids = ix.insert_batch(points())
    ix.labels()
    ix.delete_batch(ids[:30])
    ix.labels()
    rebuilds = [s for s in ix.obs.tracer.export()
                if s["name"] == "soa.rebuild"]
    assert len(rebuilds) == ix.engine.n_epoch_rebuilds == 2
    assert not any(s["args"]["on_device"] for s in rebuilds)
    assert all(s["args"]["edges"] > 0 for s in rebuilds)
    m = ix.obs.snapshot()["metrics"]
    assert m["soa.sv_rounds"]["value"] == sum(
        s["args"]["rounds"] for s in rebuilds)
    assert set(m) == {"soa.sv_rounds"} | set(REPLAY)


# what a merge of the epoch counts: merges served, rows folded in
MERGE = ("soa.epoch.merges", "soa.epoch.merge_rows")


@pytest.mark.parametrize("backend", ["soa", "soa-device", "approx"])
def test_insert_after_a_read_merges_and_expire_rebuilds(backend):
    """insert / labels / insert / labels rebuilds the epoch once and merges
    it once, in a span beside ``soa.rebuild``; an expiry drops it, and the
    next read rebuilds in full.  The counters count the merge that ran."""
    ix = build_index(cfg(backend))
    X = points(400)
    ids = ix.insert_batch(X[:300])  # capacity 512: the next insert fits
    ix.labels()
    ids += ix.insert_batch(X[300:])
    rows = sum(map(len, ix.engine._pending))
    assert rows > 0
    ix.labels()
    ix.label(ids[-1])  # the merged epoch answers without another span
    spans = ix.obs.tracer.drain_export()
    got = tree(spans)
    assert got.count(("soa.rebuild", None)) == 1
    assert got.count(("soa.epoch.merge", None)) == 1
    assert not any(parent == "soa.epoch.merge" for _, parent in got)
    (merge,) = [s for s in spans if s["name"] == "soa.epoch.merge"]
    assert merge["args"]["rows"] == rows and merge["args"]["nodes"] >= 1
    m = ix.obs.snapshot()["metrics"]
    assert m["soa.epoch.merges"]["value"] == 1
    assert m["soa.epoch.merge_rows"]["value"] == rows
    assert ix.stats()["n_epoch_merges"] == ix.engine.n_epoch_merges == 1
    assert ix.stats()["n_epoch_rebuilds"] == ix.engine.n_epoch_rebuilds == 1

    ix.delete_batch(ids[:50])
    ix.labels()
    got = tree(ix.obs.tracer.drain_export())
    assert got.count(("soa.rebuild", None)) == 1
    assert ("soa.epoch.merge", None) not in got
    assert ix.obs.snapshot()["metrics"]["soa.epoch.merges"]["value"] == 1
    assert ix.engine.n_epoch_rebuilds == 2


def test_insert_without_a_cached_epoch_records_nothing():
    """With no epoch cached, as in a stream that never reads, an insert
    keeps nothing for a merge and no merge counter exists."""
    ix = build_index(cfg())
    X = points(400)
    ix.insert_batch(X[:200])
    ix.insert_batch(X[200:])
    assert ix.engine._comp is None and not ix.engine._pending
    ix.labels()
    assert ix.engine.n_epoch_rebuilds == 1 and ix.engine.n_epoch_merges == 0
    assert not set(MERGE) & set(ix.obs.snapshot()["metrics"])


@pytest.mark.parametrize("backend", ["soa", "soa-device", "approx"])
def test_replay_counters_exist_only_with_obs(backend):
    """Every insert counts the rows its replay examined, 0 included, so
    both counters exist once ``obs`` is on; with it off there are none."""
    on = build_index(cfg(backend, obs=True))
    off = build_index(cfg(backend, obs=False))
    for ix in (on, off):
        ix.insert_batch(points(40))
    m = on.obs.snapshot()["metrics"]
    assert all(m[name]["value"] >= 0 for name in REPLAY)
    assert off.obs.snapshot()["metrics"] == {}


@pytest.mark.parametrize("backend", ["soa", "soa-device", "approx"])
def test_obs_changes_no_answer(backend):
    rng = np.random.default_rng(5)
    X = points(500, seed=5)
    kw = {"sample_rate": 0.5} if backend == "approx" else {}
    on = build_index(cfg(backend, obs=True, **kw))
    off = build_index(cfg(backend, obs=False, **kw))
    on.drain_deltas(), off.drain_deltas()
    alive = []
    for lo in range(0, len(X), 100):
        got = on.insert_batch(X[lo:lo + 100])
        assert got == off.insert_batch(X[lo:lo + 100])
        alive += got
        assert on.drain_deltas() == off.drain_deltas()
        dels = [alive.pop(int(rng.integers(len(alive)))) for _ in range(25)]
        on.delete_batch(dels)
        off.delete_batch(dels)
        assert on.drain_deltas() == off.drain_deltas()
        assert [on.label(i) for i in alive] == [off.label(i) for i in alive]
        assert on.labels() == off.labels()
    assert any(s["name"] == "soa.insert" for s in on.obs.tracer.export())
    assert off.obs.snapshot() == {"proc": "null", "metrics": {},
                                  "spans": [], "spans_dropped": 0}


def test_obs_off_counts_no_bytes_and_opens_no_span(monkeypatch):
    def refuse(*_a, **_kw):
        raise AssertionError("instrumentation ran with obs off")

    monkeypatch.setattr(SoADynamicDBSCAN, "_count_copies", refuse)
    monkeypatch.setattr(Tracer, "span", refuse)
    ix = build_index(cfg(obs=False))
    ix.drain_deltas()
    ids = ix.insert_batch(points())
    ix.delete_batch(ids[:50])
    ix.labels()
    ix.drain_deltas()
