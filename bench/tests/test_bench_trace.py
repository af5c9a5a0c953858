"""``trace_reduce`` on small traces recorded on one TPU v5e (traced
``blobs-d10.query`` runs at a 4,000-point live window, one second, with
the engine's spans off and on), and on intervals whose busy and idle time
and whose nesting are known by construction."""

import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import trace_reduce  # noqa: E402

TRACE = BENCH / "testdata" / "blobs_query_v5e.xplane.pb.gz"
# what the reduction gave on TRACE before it read engine spans
REDUCED = BENCH / "testdata" / "blobs_query_v5e.reduced.json"
# the same run with the engine's spans on, a 0.6 s window (one second
# made a file of 396 KB); its constants read off it once
OBS_TRACE = BENCH / "testdata" / "blobs_query_obs_v5e.xplane.pb.gz"
OBS_WINDOW_S, OBS_BUSY_S, OBS_STEPS = 0.616135222, 0.144033768, 21
# read off this trace once, by the reduction as committed
WINDOW_S, BUSY_S = 1.00742502, 0.003996449
INSERTS, HASH_S = 42, 0.000487525


@pytest.fixture(scope="module")
def reduced():
    return trace_reduce.reduce(str(TRACE))


def test_recorded_trace_busy_window_and_programs(reduced):
    assert reduced["window_s"] == pytest.approx(WINDOW_S, abs=1e-9)
    assert reduced["busy_s"] == pytest.approx(BUSY_S, abs=1e-9)
    progs = reduced["programs"]
    assert set(progs) == {"jit_lsh_hash", "jit_slot_counts",
                          "jit_bucket_core_stats"}
    # one execution of each program per insert call in the window
    assert {p["count"] for p in progs.values()} == {INSERTS}
    assert progs["jit_lsh_hash"]["seconds"] == pytest.approx(HASH_S,
                                                              abs=1e-9)


def test_recorded_trace_breakdown(reduced):
    ops = reduced["breakdown"]["device_ops"]
    idle = reduced["breakdown"]["idle_gaps"]
    assert 0 < len(ops) <= 10 and 0 < len(idle) <= 10
    assert all(name.split(":")[0].startswith("jit_") for name, _ in ops)
    # idle time, split among host spans, adds up to the window less busy
    assert sum(s for _, s in idle) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"], rel=1e-6)
    assert {name for name, _ in idle} >= {"bench.insert", "bench.expire",
                                          "bench.label"}


@pytest.fixture(scope="module")
def reduced_obs():
    return trace_reduce.reduce(str(OBS_TRACE))


def test_recorded_obs_trace_engine_phases(reduced_obs):
    r = reduced_obs
    assert r["window_s"] == pytest.approx(OBS_WINDOW_S, abs=1e-9)
    assert r["busy_s"] == pytest.approx(OBS_BUSY_S, abs=1e-9)
    eng, progs = r["engine"], r["programs"]
    # one insert and one expire a step, a rebuild after each; each span
    # matched by its device program
    assert eng["soa.insert"]["count"] == eng["soa.expire"]["count"] == (
        progs["jit_lsh_hash"]["count"]) == OBS_STEPS
    assert eng["soa.rebuild"]["count"] == (
        progs["jit_core_components"]["count"]) == 2 * OBS_STEPS
    for name, st in list(eng.items()) + list(r["engine_paths"].items()):
        assert 0 <= st["self_seconds"] <= st["seconds"], name
    for path, st in r["engine_paths"].items():
        assert st["count"] <= eng[path.rsplit("/", 1)[-1]]["count"]
    # a rebuild's self time is what its two phases leave of it
    assert eng["soa.rebuild"]["self_seconds"] + sum(
        eng[k]["seconds"] for k in ("soa.rebuild.edges", "soa.rebuild.sv")
    ) == pytest.approx(eng["soa.rebuild"]["seconds"], rel=1e-9)


def test_recorded_obs_trace_idle_by_phase(reduced_obs):
    r = reduced_obs
    idle, gaps = r["idle"], r["breakdown"]["idle_gaps"]
    assert sum(idle.values()) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-9)
    assert sum(s for _, s in gaps) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-9)
    assert len(gaps) == 10 and gaps[-1][0] == trace_reduce.IDLE_REST
    assert any(n.startswith("soa.insert.") for n in idle)
    assert any(n.startswith("soa.rebuild.") for n in idle)
    # the fetches under the insert wait on the device: the largest gap
    assert gaps[0][0] == "soa.device.fetch"


def test_union_clip_and_gaps_by_construction():
    iv = [(5, 8), (0, 2), (1, 3), (7, 9)]
    assert trace_reduce._union(iv) == [(0, 3), (5, 9)]
    assert trace_reduce._clip([(0, 3), (5, 9)], 2, 6) == [(2, 3), (5, 6)]
    assert trace_reduce._top({"a": 1.0, "b": 3.0, "c": 2.0}, 2) == [
        ["b", 3.0], ["c", 2.0]]
    assert trace_reduce._top({"a": 1.0, "b": 3.0, "c": 2.0}, 2, "rest") == [
        ["b", 3.0], ["rest", 3.0]]
    assert trace_reduce._top({"a": 1.0, "b": 3.0}, 2, "rest") == [
        ["b", 3.0], ["a", 1.0]]


def test_trace_without_engine_spans_reduces_as_before(reduced):
    before = json.loads(REDUCED.read_text())
    assert reduced["engine"] == {} and reduced["engine_paths"] == {}
    assert {k: v for k, v in reduced.items()
            if k not in ("engine", "engine_paths", "idle")} == before
    assert sorted(map(list, reduced["idle"].items())) == sorted(
        before["breakdown"]["idle_gaps"])


def test_phases_nest_by_construction():
    line = [(0, 100, "soa.a"), (10, 40, "soa.b"), (20, 30, "soa.c"),
            (50, 70, "soa.b"), (60, 120, "soa.c")]   # cut at 70
    by_name, by_path, inner = trace_reduce._phases([line])
    assert by_name["soa.a"] == {"count": 1, "seconds": 100e-9,
                                "self_seconds": 50e-9}
    assert by_name["soa.b"]["count"] == 2
    assert by_name["soa.b"]["seconds"] == pytest.approx(50e-9)
    assert by_name["soa.b"]["self_seconds"] == pytest.approx(30e-9)
    assert by_path["soa.a/soa.b/soa.c"]["seconds"] == pytest.approx(20e-9)
    assert inner == [(0, 10, "soa.a"), (10, 20, "soa.b"), (20, 30, "soa.c"),
                     (30, 40, "soa.b"), (40, 50, "soa.a"), (50, 60, "soa.b"),
                     (60, 70, "soa.c"), (70, 100, "soa.a")]
    # a second line over the same instants gives them to the first
    _, _, both = trace_reduce._phases([line, [(90, 110, "soa.d")]])
    assert both[-2:] == [(70, 100, "soa.a"), (100, 110, "soa.d")]


def test_idle_split_by_construction():
    inner = [(10, 20, "soa.a"), (30, 50, "soa.b"), (95, 200, "soa.c")]
    host = [(0, 60, "bench.insert"), (70, 90, "bench.expire")]
    idle = trace_reduce._split_idle([(0, 100), (150, 160)], inner, host)
    assert idle == pytest.approx({
        "soa.a": 10e-9, "soa.b": 20e-9, "soa.c": 15e-9,
        "bench.insert": 30e-9, "bench.expire": 20e-9,
        "host outside bench spans": 15e-9})
    assert sum(idle.values()) == pytest.approx(110e-9)
