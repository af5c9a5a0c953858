"""The ``covertype-d54.ingest`` cell off the chip: its replay reader
``replay_rows_per_step`` by construction, and a traced harness run at a
small live window that is correct, reads every metric of the cell that a
CPU can give, and runs the grab and scan replays over a window that
holds border and noise points."""

import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402

from repro.api import NOISE, build_index  # noqa: E402

CELL = "covertype-d54.ingest"
REPLAY = {"soa.replay.grab_rows": 1200, "soa.replay.scan_rows": 300}


def _run(steps=4, counters=REPLAY, dropped=0, traced=True):
    trace = {"engine": {}, "engine_paths": {}} if traced else None
    return harness.Run(CELL, {}, {}, 1000, {}, [], trace, None, steps,
                       {k: v * steps for k, v in counters.items()}, dropped)


def _read(run):
    return harness._load_module(
        BENCH / "metrics" / "replay_rows_per_step.py").read(run)


@pytest.mark.parametrize("steps", [1, 4])
def test_replay_rows_per_step_by_construction(steps):
    assert _read(_run(steps)) == pytest.approx(1500.0, rel=1e-12)


def test_replay_rows_per_step_reads_nothing_it_cannot_trust():
    assert _read(_run(dropped=1)) is None
    assert _read(_run(traced=False)) is None
    assert _read(_run(counters={"soa.h2d_bytes": 10})) is None
    assert _read(_run(steps=0)) is None


def test_the_cell_reads_only_its_own_rebuild_free_metrics():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = harness.find_cell(CELL)
    assert cell.chips == 1 and cell.config["name"] == "covertype-d54"
    assert {m["name"] for m in cell.end_to_end} == {"setup_s",
                                                    "updates_per_s"}
    names = {m["name"] for m in cell.per_layer}
    assert "replay_rows_per_step" in names
    assert not names & {"rebuild_ms", "rebuild_edges_ms",
                        "rebuild_device_ms"}
    conf = next(c for c in spec["configs"] if c["name"] == "covertype-d54")
    assert conf["reduced"] == ["live_window"]


def test_traced_small_window_run_reads_the_cell():
    """At a 2,000-point window the replays run on every insert (borders
    and orphans are present), the run is correct, and every per-layer
    metric of the cell is read but those of the device trace, which a
    CPU run has none of."""
    made = []

    def make_index(cfg):
        made.append(build_index(cfg))
        return made[-1]

    r = harness.run_cell(CELL, 2**33 + 7, 0.5, True,
                         overrides={"live_window": 2000},
                         make_index=make_index, log=lambda s: None)
    assert r["correct"] is True, r["checks"]
    assert all(c["value"] == 0 for c in r["checks"].values())
    cell = harness.find_cell(CELL)
    want = {m["name"] for m in cell.per_layer
            if m["source"] != "device_trace"}
    assert set(r["metrics"]) == want
    assert all(r["metrics"][n]["value"] > 0 for n in want)
    ix = made[0]
    assert ix.obs.tracer.dropped == 0
    eng = ix.engine
    assert eng.n_grab_events > 0 and eng.n_scan_events > 0
    labels = ix.labels()
    border = [i for i, lab in labels.items()
              if lab != NOISE and not ix.is_core(i)]
    assert border and NOISE in labels.values()
