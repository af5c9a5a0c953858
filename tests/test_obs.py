"""Tests for repro.obs (PR 7): metrics semantics, span parenting,
exporters, the trace-context sidecar on the wire codec, and the
end-to-end acceptance run — one traced ``insert_batch`` over
``transport="process"`` at S=2 must produce a Chrome trace whose
shard-side spans carry the coordinator's trace id across the
socketpair, parented under the per-shard wire spans."""

import json
import subprocess
import sys
import time

import numpy as np
import pytest

from repro.api import ClusterConfig, build_index
from repro.data import blobs
from repro.obs import (
    NULL_COUNTER,
    NULL_GAUGE,
    NULL_HISTOGRAM,
    NULL_OBS,
    Histogram,
    MetricsRegistry,
    Obs,
    Span,
    Tracer,
    histogram_summary,
    load_chrome,
    make_obs,
    merge_snapshots,
    snapshot_json,
    span_stats,
    to_chrome,
    to_prometheus,
    write_chrome,
)
from repro.obs.cli import main as obs_main
from repro.service.messages import StatsReq
from repro.service.codec import decode, encode


def cfg_for(shards, transport="local", obs=False, **kw):
    base = dict(d=4, k=6, t=6, eps=0.45, seed=0, backend="sharded",
                inner_backend="dynamic")
    base.update(kw)
    return ClusterConfig(shards=shards, transport=transport, obs=obs, **base)


# ---------------------------------------------------------------------- #
# metrics
# ---------------------------------------------------------------------- #
class TestMetrics:
    def test_counter_gauge_basics(self):
        reg = MetricsRegistry()
        c = reg.counter("ops")
        c.inc()
        c.inc(4)
        g = reg.gauge("depth")
        g.set(3)
        g.set(7.5)
        snap = reg.snapshot()
        assert snap["ops"] == {"type": "counter", "value": 5}
        assert snap["depth"] == {"type": "gauge", "value": 7.5}

    def test_registry_is_idempotent_and_kind_checked(self):
        reg = MetricsRegistry()
        assert reg.counter("x") is reg.counter("x")
        with pytest.raises(TypeError):
            reg.histogram("x")  # same name, different kind

    def test_histogram_exact_moments_and_bucketed_percentiles(self):
        h = Histogram("lat_us")
        for v in (3.0, 5.0, 100.0, 900.0):
            h.observe(v)
        s = h.snapshot()
        assert s["count"] == 4 and s["sum"] == pytest.approx(1008.0)
        assert s["min"] == 3.0 and s["max"] == 900.0
        # log2 buckets: percentiles are bucket midpoints clamped to the
        # exact [min, max] envelope
        assert s["min"] <= s["p50"] <= s["p99"] <= s["max"]
        # the bucket upper bounds are powers of two covering max
        bounds = [float(b) for b in s["buckets"]]
        assert all(b == 2.0 ** round(np.log2(b)) for b in bounds)
        assert max(bounds) >= 900.0 and sum(s["buckets"].values()) == 4

    def test_histogram_percentile_edge_cases(self):
        h = Histogram("empty")
        assert h.percentile(50) == 0.0
        h.observe(0.0)  # non-positive values land in the smallest bucket
        assert h.snapshot()["count"] == 1

    def test_timer_records_elapsed_microseconds(self):
        h = Histogram("t_us")
        with h.timer():
            time.sleep(0.01)
        s = h.snapshot()
        assert s["count"] == 1
        assert 5_000 <= s["sum"] <= 2_000_000  # 10ms sleep, generous ceiling

    def test_null_instruments_are_inert_singletons(self):
        obs = make_obs(False)
        assert obs is NULL_OBS and not obs.enabled
        assert obs.counter("a") is NULL_COUNTER
        assert obs.gauge("b") is NULL_GAUGE
        assert obs.histogram("c") is NULL_HISTOGRAM
        obs.counter("a").inc(10)
        obs.gauge("b").set(1)
        obs.histogram("c").observe(5)
        with obs.histogram("c").timer():
            pass
        with obs.tracer.span("nope"):
            assert obs.tracer.context() is None
        snap = obs.snapshot()
        assert snap["metrics"] == {} and snap["spans"] == []
        assert obs.drain() == snap

    def test_make_obs_enabled_returns_live_handle(self):
        obs = make_obs(True, proc="worker3")
        assert obs.enabled and obs.proc == "worker3"
        obs.counter("n").inc()
        assert obs.snapshot()["metrics"]["n"]["value"] == 1


# ---------------------------------------------------------------------- #
# tracing
# ---------------------------------------------------------------------- #
class TestTrace:
    def test_nested_spans_share_trace_and_link_parent(self):
        tr = Tracer(proc="main")
        with tr.span("outer") as a:
            with tr.span("inner") as b:
                assert b.trace_id == a.trace_id
                assert b.parent_id == a.span_id
        out = tr.drain_export()
        by_name = {s["name"]: s for s in out}
        assert by_name["inner"]["parent"] == by_name["outer"]["span"]
        assert by_name["outer"]["parent"] is None
        assert by_name["inner"]["dur"] >= 0

    def test_sibling_spans_get_distinct_ids(self):
        tr = Tracer()
        with tr.span("root"):
            with tr.span("a"):
                pass
            with tr.span("b"):
                pass
        spans = {s["name"]: s for s in tr.drain_export()}
        assert spans["a"]["span"] != spans["b"]["span"]
        assert spans["a"]["parent"] == spans["b"]["parent"]

    def test_adopt_parents_under_a_remote_context(self):
        # the server side of the wire: adopt() installs the coordinator's
        # (trace, span) so the first local span parents across processes
        coord = Tracer(proc="coordinator")
        with coord.span("coord.op") as root:
            ctx = root.wire_ctx()
        shard = Tracer(proc="shard0")
        with shard.adopt(ctx):
            with shard.span("shard.op"):
                pass
        (sp,) = shard.drain_export()
        assert sp["trace"] == ctx["t"] and sp["parent"] == ctx["s"]
        # adoption is scoped: after the block the ambient parent is gone
        assert shard.context() is None

    def test_ingest_round_trips_remote_summaries(self):
        remote = Tracer(proc="shard1")
        with remote.span("shard.insert_batch", n=7):
            pass
        summaries = remote.drain_export()
        assert remote.export() == []  # drain empties the buffer
        local = Tracer(proc="coordinator")
        local.ingest(summaries)
        (sp,) = local.export()
        assert sp["name"] == "shard.insert_batch" and sp["proc"] == "shard1"
        assert sp["args"]["n"] == 7

    def test_span_export_round_trip(self):
        tr = Tracer()
        with tr.span("x", k=1) as sp:
            pass
        d = sp.export()
        back = Span.from_export(d)
        assert back.export() == d

    def test_buffer_capacity_counts_drops(self):
        tr = Tracer(capacity=2)
        for i in range(5):
            with tr.span(f"s{i}"):
                pass
        assert len(tr.export()) == 2 and tr.dropped == 3

    def test_enabled_span_is_a_profiler_host_event(self, tmp_path):
        # under a profiler session a span is also a host event on the
        # profiler's clock; the null tracer's spans record nothing there
        import jax
        from jax.profiler import ProfileData

        tr = Tracer()
        with jax.profiler.trace(str(tmp_path)):
            with tr.span("obs.test.outer", n=2):
                with tr.span("obs.test.inner"):
                    pass
                with NULL_OBS.tracer.span("obs.test.null"):
                    pass
        (path,) = tmp_path.glob("**/*.xplane.pb")
        pd = ProfileData.from_file(str(path))
        names = {ev.name.split("#")[0] for plane in pd.planes
                 if plane.name.startswith("/host:")
                 for line in plane.lines for ev in line.events}
        assert {"obs.test.outer", "obs.test.inner"} <= names
        assert "obs.test.null" not in names
        # the in-memory buffer is unchanged by the annotation
        assert [s["name"] for s in tr.export()] == ["obs.test.inner",
                                                    "obs.test.outer"]

    def test_import_and_spans_leave_jax_unimported(self):
        # shard workers import repro.obs; neither the import nor a span,
        # enabled or null, may pull JAX into a process that lacks it
        code = ("import sys\n"
                "import repro.obs as o\n"
                "with o.Tracer().span('a', n=1):\n"
                "    pass\n"
                "with o.NULL_TRACER.span('b'):\n"
                "    pass\n"
                "print('jax' in sys.modules)\n")
        out = subprocess.run([sys.executable, "-c", code],
                             capture_output=True, text=True)
        assert out.returncode == 0, out.stderr
        assert out.stdout.strip() == "False"


# ---------------------------------------------------------------------- #
# exporters
# ---------------------------------------------------------------------- #
def _sample_obs():
    obs = Obs(proc="coordinator")
    obs.counter("bridge.rep_cache_hit").inc(3)
    obs.gauge("serving.queue_depth").set(2)
    h = obs.histogram("coord.insert_batch_us")
    for v in (10.0, 40.0, 300.0):
        h.observe(v)
    with obs.tracer.span("coord.insert_batch", n=3):
        with obs.tracer.span("bridge.merge"):
            pass
    return obs


class TestExport:
    def test_merge_snapshots_prefixes_proc(self):
        a, b = Obs(proc="coordinator"), Obs(proc="shard0")
        a.counter("n").inc()
        b.counter("n").inc(2)
        merged = merge_snapshots([a.snapshot(), b.snapshot()])
        assert merged["metrics"]["coordinator/n"]["value"] == 1
        assert merged["metrics"]["shard0/n"]["value"] == 2
        assert merged["spans"] == [] and merged["spans_dropped"] == 0

    def test_prometheus_exposition(self):
        merged = merge_snapshots([_sample_obs().snapshot()])
        text = to_prometheus(merged["metrics"])
        assert "repro_coordinator_bridge_rep_cache_hit_total 3" in text
        assert "repro_coordinator_serving_queue_depth 2" in text
        # histogram: cumulative buckets ending at +Inf, plus sum/count
        assert 'le="+Inf"} 3' in text
        assert "repro_coordinator_coord_insert_batch_us_count 3" in text
        lines = [ln for ln in text.splitlines() if "_bucket" in ln]
        counts = [int(ln.rsplit(" ", 1)[1]) for ln in lines]
        assert counts == sorted(counts)  # cumulative, monotone

    def test_chrome_trace_structure_and_roundtrip(self, tmp_path):
        obs = _sample_obs()
        doc = to_chrome(obs.snapshot()["spans"])
        kinds = {e["ph"] for e in doc["traceEvents"]}
        assert kinds == {"M", "X"}
        meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
        assert meta[0]["name"] == "process_name"
        assert meta[0]["args"]["name"] == "coordinator"
        path = tmp_path / "trace.json"
        write_chrome(path, obs.snapshot()["spans"])
        events = load_chrome(path)
        assert {e["name"] for e in events} == {"coord.insert_batch",
                                              "bridge.merge"}
        by_name = {e["name"]: e for e in events}
        assert (by_name["bridge.merge"]["args"]["parent"]
                == by_name["coord.insert_batch"]["args"]["span"])

    def test_span_stats_and_histogram_summary(self):
        obs = _sample_obs()
        events = to_chrome(obs.snapshot()["spans"])["traceEvents"]
        rows = span_stats([e for e in events if e["ph"] == "X"])
        assert [r["op"] for r in rows][0] in ("coord.insert_batch",
                                              "bridge.merge")
        assert all(r["count"] == 1 and r["p50_us"] <= r["p99_us"]
                   for r in rows)
        summ = histogram_summary(merge_snapshots([obs.snapshot()])["metrics"])
        row = summ["coordinator/coord.insert_batch_us"]
        assert row["count"] == 3 and row["mean"] == pytest.approx(350.0 / 3)

    def test_snapshot_json_is_json(self):
        doc = json.loads(snapshot_json(_sample_obs().snapshot()))
        assert doc["proc"] == "coordinator"


# ---------------------------------------------------------------------- #
# wire sidecar: absent context is bit-identical, present context ships
# ---------------------------------------------------------------------- #
class TestWireSidecar:
    def test_untraced_encode_is_bit_identical(self):
        ref = encode(StatsReq(want_obs=False))
        msg = StatsReq(want_obs=False)
        assert msg.trace_ctx is None and msg.span_summary is None
        assert encode(msg) == ref
        assert b"__trace__" not in ref and b"__spans__" not in ref

    def test_trace_context_round_trips_and_changes_bytes(self):
        plain = encode(StatsReq())
        msg = StatsReq()
        msg.trace_ctx = {"t": 12345, "s": 678}
        traced = encode(msg)
        assert traced != plain
        back = decode(traced)
        assert back.trace_ctx == {"t": 12345, "s": 678}
        # the sidecar is per-message state, not class state
        assert StatsReq().trace_ctx is None

    def test_span_summary_round_trips(self):
        tr = Tracer(proc="shard0")
        with tr.span("shard.labels"):
            pass
        msg = StatsReq()
        msg.span_summary = tr.drain_export()
        back = decode(encode(msg))
        assert back.span_summary is not None
        (sp,) = back.span_summary
        assert sp["name"] == "shard.labels" and sp["proc"] == "shard0"


# ---------------------------------------------------------------------- #
# end to end: the acceptance run
# ---------------------------------------------------------------------- #
class TestEndToEnd:
    def test_disabled_obs_is_the_null_object(self):
        ix = build_index(cfg_for(2, "local", obs=False))
        try:
            assert ix.obs is NULL_OBS
            ix.insert_batch(np.zeros((4, 4)))
            assert ix.obs_snapshot() == []
        finally:
            ix.close()

    def test_local_transport_traces_and_metrics(self):
        X, _ = blobs(n=80, d=4, n_clusters=2, cluster_std=0.2, seed=2)
        ix = build_index(cfg_for(2, "local", obs=True, seed=2))
        try:
            ids = ix.insert_batch(X)
            ix.labels()
            ix.delete_batch(ids[:10])
            ix.obs_refresh()
            merged = merge_snapshots(ix.obs_snapshot())
            names = set(merged["metrics"])
            assert "coordinator/coord.insert_batch_us" in names
            assert "coordinator/rpc.shard0_us" in names
            assert "coordinator/rpc.shard1_us" in names
            assert "coordinator/bridge.epoch" in names
            assert "coordinator/router.load_skew" in names
            # shard-side registries ride back through StatsReq(want_obs)
            assert any(n.startswith("shard0/") for n in names)
            span_names = {s["name"] for s in merged["spans"]}
            assert {"coord.insert_batch", "bridge.insert",
                    "bridge.merge", "shard.insert_batch"} <= span_names
        finally:
            ix.close()

    def test_process_transport_spans_cross_the_socketpair(self, tmp_path):
        """Acceptance criterion: a traced insert_batch at S=2 over the
        process transport renders coordinator -> wire -> shard spans with
        correct parentage and one shared trace id per coordinator op."""
        X, _ = blobs(n=60, d=4, n_clusters=2, cluster_std=0.2, seed=4)
        ix = build_index(cfg_for(2, "process", obs=True, seed=4))
        try:
            ix.insert_batch(X)
            ix.labels()
            path = ix.write_trace(tmp_path / "trace.json")
        finally:
            ix.close()
        doc = json.loads(path.read_text())
        lane = {e["pid"]: e["args"]["name"]
                for e in doc["traceEvents"] if e["ph"] == "M"}
        events = load_chrome(path)
        procs = {lane[e["pid"]] for e in events}
        assert {"coordinator", "shard0", "shard1"} <= procs
        by_span = {e["args"]["span"]: e for e in events}
        wire = [e for e in events if e["name"].startswith("wire.shard")]
        assert {e["name"] for e in wire} >= {"wire.shard0", "wire.shard1"}
        # startup hellos and obs pulls trace as their own roots; the op
        # spans issued inside coordinator ops are the parentage criterion
        shard_spans = [e for e in events
                       if lane[e["pid"]].startswith("shard")
                       and e["name"] in ("shard.insert_batch",
                                         "shard.labels")]
        assert shard_spans, "no server-side spans shipped back"
        for e in shard_spans:
            parent = by_span.get(e["args"]["parent"])
            assert parent is not None, e["name"]
            assert parent["name"].startswith("wire.shard")
            # one trace id flows coordinator -> wire -> shard
            assert e["args"]["trace"] == parent["args"]["trace"]
            root = by_span[parent["args"]["parent"]]
            assert lane[root["pid"]] == "coordinator"
            assert root["args"]["trace"] == e["args"]["trace"]

    def test_cli_report_renders_per_op_latency(self, tmp_path, capsys):
        ix = build_index(cfg_for(2, "local", obs=True))
        try:
            ix.insert_batch(np.random.default_rng(0).normal(size=(40, 4)))
            ix.labels()
            path = ix.write_trace(tmp_path / "t.json")
        finally:
            ix.close()
        assert obs_main(["report", str(path)]) == 0
        out = capsys.readouterr().out
        assert "p50" in out and "p99" in out
        assert "coord.insert_batch" in out
        # --json mode emits machine-readable rows
        assert obs_main(["report", str(path), "--json"]) == 0
        rows = json.loads(capsys.readouterr().out)
        assert any(r["op"] == "coord.insert_batch" for r in rows)

    def test_cli_module_entrypoint(self, tmp_path):
        obs = _sample_obs()
        path = tmp_path / "trace.json"
        write_chrome(path, obs.snapshot()["spans"])
        out = subprocess.run(
            [sys.executable, "-m", "repro.obs", "report", str(path)],
            capture_output=True, text=True)
        assert out.returncode == 0, out.stderr
        assert "coord.insert_batch" in out.stdout

    def test_cli_prom_subcommand(self, tmp_path, capsys):
        path = tmp_path / "snap.json"
        path.write_text(snapshot_json(_sample_obs().snapshot()))
        assert obs_main(["prom", str(path)]) == 0
        out = capsys.readouterr().out
        assert "repro_coordinator_bridge_rep_cache_hit_total 3" in out

    def test_bridge_cache_counters_move(self):
        X, _ = blobs(n=120, d=4, n_clusters=3, cluster_std=0.2, seed=7)
        ix = build_index(cfg_for(2, "local", obs=True, seed=7))
        try:
            ids = ix.insert_batch(X)
            # point queries drive bridge.resolve: the first after a
            # mutation rebuilds the quotient (miss), repeats hit the
            # epoch-stamped cache
            ix.label(ids[0])
            ix.label(ids[1])
            ix.label(ids[2])
            m = ix.obs.snapshot()["metrics"]
            assert m["bridge.quotient_cache_miss"]["value"] >= 1
            assert m["bridge.quotient_cache_hit"]["value"] >= 1
            assert m["coord.label_us"]["count"] == 3
        finally:
            ix.close()
