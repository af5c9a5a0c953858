"""The readers of the connectivity epoch's merge, ``epoch_merge_ms`` and
``epoch_merge_share``: on a hand-built run whose values are known by
construction, ``None`` where the run was not traced, its tracer dropped
spans or no merge ran, and on a traced harness run of ``blobs-d10.query``
off the chip, where the read after each insert merges the epoch and the
read after each expiry rebuilds it."""

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402
import trace_reduce  # noqa: E402

MS = 1_000_000  # ns

# one step's epoch spans, in ms from the step's start: the read after the
# expiry rebuilds, the read after the insert merges
STEP = [
    (0, 100, "soa.rebuild"),
    (0, 7, "soa.rebuild.edges"),
    (10, 90, "soa.rebuild.sv"),
    (200, 203, "soa.epoch.merge"),
]
EXPECTED = [("epoch_merge_ms", 3.0), ("epoch_merge_share", 0.5)]


def _run(steps=2, leave_out=(), dropped=0, traced=True):
    events = [(int((1000 * i + s) * MS), int((1000 * i + e) * MS), name)
              for i in range(steps) for s, e, name in STEP
              if name not in leave_out]
    by_name, by_path, _ = trace_reduce._phases([events])
    trace = ({"engine": by_name, "engine_paths": by_path} if traced
             else None)
    return harness.Run("test.mix", {}, {}, 500, {}, [], trace, None,
                       steps, {}, dropped)


def _read(name, run):
    return harness._load_module(BENCH / "metrics" / f"{name}.py").read(run)


@pytest.mark.parametrize("name,value", EXPECTED)
def test_reader_value_by_construction(name, value):
    assert _read(name, _run()) == pytest.approx(value, rel=1e-12)
    assert _read(name, _run(steps=5)) == pytest.approx(value, rel=1e-12)


@pytest.mark.parametrize("name,_value", EXPECTED)
def test_reader_is_none_when_spans_were_dropped_or_untraced(name, _value):
    assert _read(name, _run(dropped=1)) is None
    assert _read(name, _run(traced=False)) is None


@pytest.mark.parametrize("name,_value", EXPECTED)
def test_reader_is_none_when_no_merge_ran(name, _value):
    """As on the parent engine, which has no merge span."""
    assert _read(name, _run(leave_out={"soa.epoch.merge"})) is None
    assert _read(name, _run(steps=0)) is None


def test_share_is_one_when_every_read_merges():
    run = _run(leave_out={"soa.rebuild", "soa.rebuild.edges",
                          "soa.rebuild.sv"})
    assert _read("epoch_merge_share", run) == 1.0
    assert _read("epoch_merge_ms", run) == pytest.approx(3.0, rel=1e-12)


def test_traced_query_run_merges_after_inserts_and_rebuilds_after_expiry():
    r = harness.run_cell("blobs-d10.query", 2**31 + 31, 0.5, True,
                         overrides={"live_window": 2000},
                         log=lambda s: None)
    assert r["correct"] is True, r["checks"]
    assert r["metrics"]["epoch_merge_ms"]["value"] > 0
    assert r["metrics"]["epoch_merge_share"]["value"] == 0.5
