"""The readers of the engine's phases on a hand-built run: nested
``soa.*`` spans of two steps, laid out so that each reader's value is
known by construction, and ``None`` where the run was not traced, where
its tracer dropped spans, or where the span or counter it reads is
absent."""

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402
import trace_reduce  # noqa: E402

MS = 1_000_000  # ns

# one step's engine spans, in ms from the step's start
STEP = [
    (0, 100, "soa.insert"),
    (0, 30, "soa.insert.hash"),
    (10, 20, "soa.device.fetch"),
    (30, 50, "soa.insert.resolve_slots"),
    (50, 70, "soa.insert.batch_stats"),
    (55, 60, "soa.device.fetch"),
    (62, 66, "soa.device.fetch"),
    (70, 90, "soa.insert.events"),
    (80, 84, "soa.device.fetch"),      # a fetch two levels under insert
    (200, 260, "soa.expire"),
    (210, 250, "soa.expire.replay"),
    (300, 400, "soa.rebuild"),
    (300, 307, "soa.rebuild.edges"),
    (310, 390, "soa.rebuild.sv"),
    (330, 380, "soa.device.fetch"),    # the rebuild's: not device_wait_ms
    (500, 510, "soa.device.fetch"),    # under no insert
]
# one step's counts
COUNTERS = {"soa.h2d_bytes": 1500, "soa.d2h_bytes": 500, "soa.sv_rounds": 2}

# reader, its value per call (per step for copies), what it reads
EXPECTED = [
    ("resolve_slots_ms", 20.0, "soa.insert.resolve_slots"),
    ("insert_events_ms", 16.0, "soa.insert.events"),
    ("expire_replay_ms", 40.0, "soa.expire.replay"),
    ("device_wait_ms", 23.0, "soa.device.fetch"),
    ("copy_bytes_per_step", 2000.0, None),
    ("rebuild_edges_ms", 7.0, "soa.rebuild.edges"),
    ("rebuild_device_ms", 80.0, "soa.rebuild.sv"),
]


def _run(steps=2, leave_out=None, counters=COUNTERS, dropped=0,
         traced=True):
    events = [(int((1000 * i + s) * MS), int((1000 * i + e) * MS), name)
              for i in range(steps) for s, e, name in STEP
              if name != leave_out]
    by_name, by_path, _ = trace_reduce._phases([events])
    trace = ({"engine": by_name, "engine_paths": by_path} if traced
             else None)
    return harness.Run("test.mix", {}, {}, 500, {}, [], trace, None,
                       steps, {k: v * steps for k, v in counters.items()},
                       dropped)


def _read(name, run):
    return harness._load_module(BENCH / "metrics" / f"{name}.py").read(run)


@pytest.mark.parametrize("name,value,_needs", EXPECTED)
def test_reader_value_by_construction(name, value, _needs):
    assert _read(name, _run()) == pytest.approx(value, rel=1e-12)
    # the same per call whatever the number of calls
    assert _read(name, _run(steps=5)) == pytest.approx(value, rel=1e-12)


@pytest.mark.parametrize("name,_value,_needs", EXPECTED)
def test_reader_is_none_when_spans_were_dropped_or_untraced(name, _value,
                                                            _needs):
    assert _read(name, _run(dropped=1)) is None
    assert _read(name, _run(traced=False)) is None


@pytest.mark.parametrize("name,_value,needs", EXPECTED)
def test_reader_is_none_when_what_it_reads_is_absent(name, _value, needs):
    run = (_run(counters={"soa.sv_rounds": 2}) if needs is None
           else _run(leave_out=needs))
    assert _read(name, run) is None
    assert _read(name, _run(steps=0)) is None
