"""``trace_reduce`` on a small trace recorded on one TPU v5e (a traced
``blobs-d10.query`` run at a 4,000-point live window, one second), and on
intervals whose busy and idle time are known by construction."""

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import trace_reduce  # noqa: E402

TRACE = BENCH / "testdata" / "blobs_query_v5e.xplane.pb.gz"
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


def test_union_clip_and_gaps_by_construction():
    iv = [(5, 8), (0, 2), (1, 3), (7, 9)]
    assert trace_reduce._union(iv) == [(0, 3), (5, 9)]
    assert trace_reduce._clip([(0, 3), (5, 9)], 2, 6) == [(2, 3), (5, 6)]
    assert trace_reduce._top({"a": 1.0, "b": 3.0, "c": 2.0}, 2) == [
        ["b", 3.0], ["c", 2.0]]
