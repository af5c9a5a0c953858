"""The benchmark harness off the chip: cells, configurations, mixes and
per-layer metrics are found by name (new ones from new files alone),
every mix runs through the harness at a small live window and yields the
result line's keys, a traced run reads the engine's phases (a new cell
too, by entries alone), and ``bench/run.py`` refuses to run without a
TPU."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402

from repro.api import build_index  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
WORKLOADS = [w["name"] for w in SPEC["workloads"]]
RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]
# the per-layer metrics read from the engine's spans and counters
ENGINE_METRICS = ["resolve_slots_ms", "insert_events_ms", "expire_replay_ms",
                  "device_wait_ms", "copy_bytes_per_step",
                  "rebuild_edges_ms", "rebuild_device_ms"]


class _Kept:
    """``make_index`` that keeps each index it builds, to look at after
    the harness has let go of it."""

    def __init__(self):
        self.made = []

    def __call__(self, cfg):
        self.made.append(build_index(cfg))
        return self.made[-1]


@pytest.mark.parametrize("workload", WORKLOADS)
def test_cell_files_are_found_by_name(workload):
    cell = harness.find_cell(workload)
    config, mix = workload.split(".", 1)
    assert cell.config["name"] == config and cell.mix["name"] == mix
    assert (BENCH / "generators" / f"{cell.config['generator']}.py").is_file()
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in cell.per_layer:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()


def test_benchmark_json_keeps_to_its_shape():
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51
    for c in SPEC["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert set(json.loads((ROOT / c["file"]).read_text())["reduced"]) \
            == set(c["reduced"])
    for m in SPEC["end_to_end"]:
        assert m["bound"] <= 0.25 and m["source"] in ("host_clock",
                                                      "device_trace")
    for m in SPEC["per_layer"]:
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"


@pytest.mark.parametrize("workload", WORKLOADS)
def test_each_mix_runs_at_a_small_window(workload):
    kept = _Kept()
    r = harness.run_cell(workload, 2**31 + 17, 0.5, False,
                         overrides={"live_window": 2000}, make_index=kept,
                         log=lambda s: None)
    assert not kept.made[0].obs.enabled  # untraced: the engine's spans off
    assert list(r)[:5] == RESULT_KEYS and list(r)[-1] == "checks"
    assert r["correct"] is True, r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    cell = harness.find_cell(workload)
    assert set(r["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert set(r["device"]) >= {"platform", "kind", "count",
                                "memory_peak_bytes"}
    assert all(c["value"] == 0 and c["limit"] == 0
               for c in r["checks"].values())


def _tiny_cell(tmp_path):
    """A copy of the benchmark under ``tmp_path`` with a new configuration
    and mix as files, and their cell ``tiny-d4.burst`` as an entry of the
    returned ``BENCHMARK.json``."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "testdata",
                                                  "__pycache__"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    conf = json.loads((BENCH / "configs" / "blobs-d10.json").read_text())
    conf.update(name="tiny-d4", d=4, k=5, live_window=1500, batch=300)
    conf["data"].update(d=4, clusters=3, block=250)
    (tmp_path / "bench" / "configs" / "tiny-d4.json").write_text(
        json.dumps(conf))
    (tmp_path / "bench" / "mixes" / "burst.json").write_text(json.dumps({
        "name": "burst", "batch": None, "warmup_steps": 1,
        "steps": [{"op": "insert"}, {"op": "insert"}, {"op": "label",
                                                       "count": 4},
                  {"op": "expire", "count": 600}, {"op": "drain"}]}))
    spec["configs"].append({"name": "tiny-d4", "source": "test",
                            "file": "bench/configs/tiny-d4.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tiny-d4.burst", "config": "tiny-d4",
                              "traffic": "burst", "chips": 1, "why": "t"})
    return spec


def test_new_config_mix_and_metric_need_only_new_files(tmp_path):
    """A later change adds a configuration, a mix and a per-layer metric
    as files and entries, editing no file of the harness."""
    spec = _tiny_cell(tmp_path)
    (tmp_path / "bench" / "metrics" / "drain_call_ms.py").write_text(
        "def read(run):\n"
        "    calls = run.calls.get('drain')\n"
        "    return 1e3 * sum(calls) / len(calls) if calls else None\n")
    spec["per_layer"].append({"name": "drain_call_ms", "unit": "ms",
                              "better": "lower", "source": "host_clock",
                              "layer": "change feed",
                              "moves": "updates_per_s",
                              "workloads": ["tiny-d4.burst"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = harness.find_cell("tiny-d4.burst", root=tmp_path)
    assert [m["name"] for m in cell.per_layer] == ["drain_call_ms"]
    r = harness.run_cell("tiny-d4.burst", 5, 0.3, True, root=tmp_path,
                         log=lambda s: None)
    assert r["correct"] is True, r["checks"]
    assert r["metrics"]["drain_call_ms"]["value"] > 0
    assert r["checks"]["answer_mismatch"]["value"] == 0


def test_traced_run_reads_the_engine_phases():
    """A traced run turns the engine's spans on, drops none, and reads
    every engine metric of its cell; the CPU has every one of them, as
    ``soa-device`` runs its programs there too."""
    kept = _Kept()
    r = harness.run_cell("blobs-d10.query", 2**31 + 29, 0.5, True,
                         overrides={"live_window": 2000}, make_index=kept,
                         log=lambda s: None)
    assert r["correct"] is True, r["checks"]
    obs = kept.made[0].obs
    assert obs.enabled and obs.tracer.dropped == 0
    for name in ENGINE_METRICS:
        assert r["metrics"][name]["value"] > 0, name


def test_new_cell_joins_the_engine_metrics_by_entries(tmp_path):
    """A new cell reads the engine metrics once its name is added to their
    ``workloads`` lists: no reader or harness file changes."""
    spec = _tiny_cell(tmp_path)
    for m in spec["per_layer"]:
        if m["name"] in ENGINE_METRICS:
            m["workloads"].append("tiny-d4.burst")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = harness.find_cell("tiny-d4.burst", root=tmp_path)
    assert sorted(m["name"] for m in cell.per_layer) == sorted(
        ENGINE_METRICS)
    r = harness.run_cell("tiny-d4.burst", 2**32 + 3, 0.3, True,
                         root=tmp_path, log=lambda s: None)
    assert r["correct"] is True, r["checks"]
    assert sorted(r["metrics"]) == sorted(ENGINE_METRICS)
    assert all(m["value"] > 0 for m in r["metrics"].values())


def _run_py(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "blobs-d10.query",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_run_refuses_without_a_tpu():
    p = _run_py(ROOT, {})
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "needs 1 TPU" in p.stderr


def test_run_refuses_in_a_directory_with_only_the_benchmark(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    p = _run_py(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert "{" not in p.stdout
