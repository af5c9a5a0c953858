"""The benchmark's core: find a cell's files by name, run it, judge it.

A cell ``<config>.<mix>`` is an entry of ``BENCHMARK.json``'s
``workloads``.  Everything that belongs to one configuration, one traffic
mix or one per-layer metric sits in a file of its own, found by name:

  * ``bench/configs/<config>.json``: the deployment (sizes, engine,
    generator and its parameters, guarantees, precisions);
  * ``bench/generators/<generator>.py``: ``Generator(params, seed)`` with
    ``block(b)``, the stream's ``b``-th block of points;
  * ``bench/mixes/<mix>.json``: the steps a caller runs, in order
    (``insert``, ``expire``, ``drain``, ``label`` with a ``count``), the
    batch (``null`` takes the configuration's), and the warm-up steps;
  * ``bench/metrics/<metric>.py``: ``read(run) -> float | None``, one
    per-layer metric from the run's records and trace.

A traced run also turns the engine's observability on
(``ClusterConfig.obs``): ``core/soa.py`` then opens a ``soa.*`` span per
phase, which lands in the profiler's trace, and counts its copies.  An
untraced run keeps it off, so the end-to-end metrics time the engine as a
user's process runs it.

So a later change adds a cell, a configuration, a mix or a metric by
adding files and entries, and edits none.  The end-to-end metrics are
computed here, from the host clock over the whole window.

A run: build the index, fill its live window through ``insert_batch`` in
the configuration's batch (draining the change feed after each batch, as
every consumer does), run the mix's warm-up steps so every shape the
window uses is compiled, then run whole steps until ``seconds`` have
passed.  Once the window has closed the run gathers what the timed path
produced (a snapshot, ``labels()``, every drained delta, every
``label()`` answer), frees the index, and compares all of it with the
plain reference (``reference.py``).
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import shutil
import statistics
import sys
import tempfile
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

import reference  # noqa: E402  (bench/reference.py)
import trace_reduce  # noqa: E402  (bench/trace_reduce.py)

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
SAMPLED_STATES = 6  # window states whose label() answers are judged


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    root: Path


def _load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem}".replace(".", "_"), path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_cell(workload: str, root: Path = ROOT) -> Cell:
    """The cell named ``workload`` with its files, from ``root``."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    entry = next((w for w in spec["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cfg = next(c for c in spec["configs"] if c["name"] == entry["config"])
    config = json.loads((root / cfg["file"]).read_text())
    mix = json.loads(
        (root / "bench" / "mixes" / f"{entry['traffic']}.json").read_text())

    def mine(m):
        return "workloads" not in m or workload in m["workloads"]

    e2e = [m for m in spec["end_to_end"] if mine(m)]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if (workload in m["workloads"] if "workloads" in m
                     else m["moves"] in names)]
    return Cell(workload, int(entry["chips"]), config, mix, e2e, per_layer,
                root)


class Stream:
    """Points by stream position, from the generator's blocks."""

    def __init__(self, gen):
        self.gen = gen
        self.size = gen.block_size
        self._blocks: Dict[int, np.ndarray] = {}

    def _block(self, b: int) -> np.ndarray:
        X = self._blocks.get(b)
        if X is None:
            if len(self._blocks) > 4:
                self._blocks.pop(min(self._blocks))
            X = self._blocks[b] = self.gen.block(b)
        return X

    def take(self, start: int, count: int) -> np.ndarray:
        b0, b1 = start // self.size, (start + count - 1) // self.size
        X = np.concatenate([self._block(b) for b in range(b0, b1 + 1)])
        off = start - b0 * self.size
        return X[off:off + count]

    def range(self, lo: int, hi: int) -> np.ndarray:
        """Positions ``[lo, hi)`` without touching the block cache."""
        if hi <= lo:
            return np.zeros((0, self.gen.d))
        b0, b1 = lo // self.size, (hi - 1) // self.size
        X = np.concatenate([self.gen.block(b) for b in range(b0, b1 + 1)])
        return X[lo - b0 * self.size:hi - b0 * self.size]


@dataclasses.dataclass
class Run:
    """What a run recorded, as the per-layer readers see it."""
    cell: str
    config: dict
    mix: dict
    batch: int
    calls: Dict[str, List[float]]   # op -> host seconds per window call
    label_after_mutation: List[float]  # first label() after a mutation
    trace: Optional[dict]           # trace_reduce.reduce(), traced runs
    peaks: Optional[dict]           # peaks.json row of the device
    steps: int = 0                  # whole steps the window ran
    # the engine's counters over the window and the spans its tracer
    # dropped there (traced runs; readers of spans trust none if any were)
    counters: Dict[str, int] = dataclasses.field(default_factory=dict)
    dropped: int = 0


class _CompileCounter:
    def __init__(self) -> None:
        self.n = 0

    def __call__(self, event: str, duration_secs: float, **_kw) -> None:
        if event == BACKEND_COMPILE_EVENT:
            self.n += 1


class _Driver:
    """Runs a mix's steps against one index and records what it saw."""

    def __init__(self, cell: Cell, index, stream: Stream, batch: int,
                 seed: int, trace: bool):
        self.cell, self.index, self.stream = cell, index, stream
        self.batch = batch
        self.rng = np.random.default_rng([seed % 2**64, 7])
        self.span = (self._annotation if trace
                     else lambda _name: contextlib.nullcontext())
        self.ids_by_pos: List[int] = []
        self.lo = self.hi = 0
        self.state = 0
        self.states: Dict[int, tuple] = {0: (0, 0)}
        self.feed: List[list] = []
        self.recording = False
        self.calls: Dict[str, List[float]] = {}
        self.label_first: List[float] = []
        self.answers: List[tuple] = []
        self.gen_s = 0.0
        self.attempted = 0
        self.updates = 0  # points inserted or expired by acknowledged calls
        self.failed = 0
        self.units = 0
        self.error: Optional[str] = None
        self._label_state = -1

    @staticmethod
    def _annotation(name):
        import jax

        return jax.profiler.TraceAnnotation(name)

    def _timed(self, op: str, units: int, fn, *args):
        self.units = units  # what a raise in this call fails
        with self.span(f"bench.{op}"):
            t0 = time.perf_counter()
            out = fn(*args)
            dt = time.perf_counter() - t0
        if self.recording:
            self.calls.setdefault(op, []).append(dt)
        return out, dt

    def fail(self, e: Exception) -> None:
        """Count the call that raised ``e`` as failed."""
        self.failed += max(self.units, 1)
        self.error = f"{type(e).__name__}: {e}"

    def _mutated(self) -> None:
        self.state += 1
        self.states[self.state] = (self.lo, self.hi)

    def insert(self, count: int) -> None:
        with self.span("bench.generate"):
            t0 = time.perf_counter()
            X = self.stream.take(self.hi, count)
            self.gen_s += time.perf_counter() - t0
        if self.recording:
            self.attempted += count
        ids, _ = self._timed("insert", count, self.index.insert_batch, X)
        if len(ids) != count:
            raise RuntimeError(f"insert_batch acknowledged {len(ids)} of "
                               f"{count} points")
        self.ids_by_pos.extend(int(i) for i in ids)
        self.hi += count
        self.updates += count * self.recording
        self._mutated()

    def expire(self, count: int) -> None:
        ids = self.ids_by_pos[self.lo:self.lo + count]
        if self.recording:
            self.attempted += len(ids)
        self._timed("expire", len(ids), self.index.delete_batch, ids)
        self.lo += len(ids)
        self.updates += len(ids) * self.recording
        self._mutated()

    def drain(self) -> None:
        deltas, _ = self._timed("drain", 0, self.index.drain_deltas)
        self.feed.append(deltas)

    def label(self, count: int) -> None:
        n = self.hi - self.lo
        for pos in self.lo + self.rng.integers(0, n, size=count):
            if self.recording:
                self.attempted += 1
            h, dt = self._timed("label", 1, self.index.label,
                                self.ids_by_pos[int(pos)])
            if self.recording:
                if self._label_state != self.state:
                    self.label_first.append(dt)
                self.answers.append((self.state, int(pos), h))
            self._label_state = self.state

    def step(self) -> None:
        for op in self.cell.mix["steps"]:
            name = op["op"]
            if name in ("insert", "expire"):
                getattr(self, name)(int(op.get("count", self.batch)))
            elif name == "drain":
                self.drain()
            elif name == "label":
                self.label(int(op["count"]))
            else:
                raise ValueError(f"unknown op {name!r} in mix")


def _counters(obs) -> Dict[str, int]:
    return {name: int(s["value"]) for name, s in obs.metrics.snapshot().items()
            if s["type"] == "counter"}


def _p95(xs: List[float]) -> float:
    return float(np.percentile(np.asarray(xs, np.float64), 95))


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             root: Path = ROOT, t_start: Optional[float] = None,
             overrides: Optional[dict] = None,
             make_index: Optional[Callable] = None,
             peaks: Optional[dict] = None,
             log=lambda s: print(s, file=sys.stderr)) -> dict:
    """Run one cell on whatever platform JAX has; returns the result line.

    ``overrides`` replaces configuration keys (the tests shrink the live
    window); ``make_index(cfg)`` builds the index in place of
    ``build_index`` (the tests plant faults under it)."""
    t_start = time.perf_counter() if t_start is None else t_start
    import jax

    from repro.api import ClusterConfig, build_index

    cell = find_cell(workload, root)
    conf = dict(cell.config, **(overrides or {}))
    gen_mod = _load_module(root / "bench" / "generators"
                           / f"{conf['generator']}.py")
    stream = Stream(gen_mod.Generator(conf["data"], seed))
    lsh_seed = int(conf["lsh_seed"])
    ccfg = ClusterConfig(d=conf["d"], k=conf["k"], t=conf["t"],
                         eps=conf["eps"], seed=lsh_seed,
                         backend=conf["backend"], obs=trace)
    batch = int(cell.mix["batch"] or conf["batch"])
    window = int(conf["live_window"])
    index = (make_index or build_index)(ccfg)
    drv = _Driver(cell, index, stream, batch, seed, trace)

    # -- set-up: fill the live window, then warm every shape up.  A call
    #    that raises, here or in the window, fails the run: the window
    #    stops and the comparison judges what the index holds.
    t0 = time.perf_counter()
    t_fill = 0.0
    try:
        drv.drain()  # the first drain starts the change feed
        fill = int(conf["batch"])
        while drv.hi < window:
            drv.insert(min(fill, window - drv.hi))
            drv.drain()
        t_fill = time.perf_counter() - t0
        for _ in range(int(cell.mix["warmup_steps"])):
            drv.step()
    except Exception as e:
        drv.fail(e)
    counter = _CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(counter)
    setup_s = time.perf_counter() - t_start
    log(f"set-up: {setup_s:.3f} s (fill of {window} points "
        f"{t_fill:.3f} s, {cell.mix['warmup_steps']} warm-up steps)")

    # -- the window
    tdir = None
    if trace:
        tdir = tempfile.mkdtemp(prefix="bench_trace_")
        # the benchmark's spans and the device planes; no Python tracer,
        # which would record every function call and slow the host
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(tdir, profiler_options=opts)
        index.obs.tracer.clear()
        counts0 = _counters(index.obs)
    drv.recording = True
    drv.gen_s = 0.0
    steps: List[float] = []
    try:
        with drv.span("bench.window"):
            t_w = time.perf_counter()
            while drv.error is None:
                ts = time.perf_counter()
                try:
                    drv.step()
                except Exception as e:
                    drv.fail(e)
                    break
                te = time.perf_counter()
                steps.append(te - ts)
                if te - t_w >= seconds:
                    break
            window_s = time.perf_counter() - t_w
    finally:
        drv.recording = False
        if trace:
            jax.profiler.stop_trace()
        jax.monitoring.unregister_event_duration_listener(counter)
    engine_counts: Dict[str, int] = {}
    dropped = 0
    if trace:
        engine_counts = {name: v - counts0.get(name, 0)
                         for name, v in _counters(index.obs).items()}
        dropped = index.obs.tracer.dropped
        log(f"engine: {len(index.obs.tracer.spans)} spans in the window, "
            f"{dropped} dropped")

    devices = jax.devices()
    used = devices[:cell.chips]
    peak_mem = 0
    for d in used:
        stats = d.memory_stats() or {}
        peak_mem = max(peak_mem, int(stats.get("peak_bytes_in_use", 0)))

    # -- what the timed path produced
    outputs = None
    try:
        if drv.error is None:
            drv.drain()
        outputs = reference.Outputs(
            state=index.snapshot()["state"], labels=index.labels(),
            feed=drv.feed, answers=drv.answers, failed=drv.failed)
    except Exception as e:
        drv.fail(e)
    del index
    drv.index = None
    gc.collect()

    tr = None
    if trace:
        tr = trace_reduce.reduce(trace_reduce.find_trace(tdir),
                                 n_devices=len(used))
        shutil.rmtree(tdir, ignore_errors=True)

    # -- the comparison with the reference
    t_ref = time.perf_counter()
    fam = reference.LSHFamily(conf["d"], conf["eps"], conf["t"], lsh_seed)
    exp = reference.Expected(np.asarray(drv.ids_by_pos, np.int64),
                             drv.lo, drv.hi, {})
    ids_live = exp.ids_by_pos[drv.lo:drv.hi]
    points = stream.range(drv.lo, drv.hi)
    ref = reference.cluster(reference.hash_keys(points, fam), conf["k"],
                            ids_live)
    pick = sorted({s for s, _, _ in drv.answers})
    if pick and outputs is not None:
        rng = np.random.default_rng([seed % 2**64, 11])
        keep = set(rng.choice(pick, size=min(SAMPLED_STATES, len(pick)),
                              replace=False).tolist())
        outputs.answers = [a for a in drv.answers if a[0] in keep]
        exp.states = {s: drv.states[s] for s in keep}
    counts = ({"failed_calls": max(drv.failed, 1)} if outputs is None else
              reference.compare(points, ref, exp, outputs,
                                state_points=stream.range, k=conf["k"],
                                fam=fam))
    ref_s = time.perf_counter() - t_ref
    if drv.error:
        log(f"a call failed: {drv.error}")

    # -- metrics
    n_steps = len(steps)
    labels_t = drv.calls.get("label", [])
    updates = drv.updates
    log(f"window: {window_s:.3f} s, {n_steps} steps, {updates} updates, "
        f"{len(labels_t)} label() calls; compiles in the window: "
        f"{counter.n}; generator {1e3 * drv.gen_s / max(n_steps, 1):.3f} "
        f"ms per step; reference check {ref_s:.3f} s, "
        f"{len(outputs.answers)} label() answers judged")
    if n_steps:
        log(f"samples: {n_steps} steps, median "
            f"{1e3 * statistics.median(steps):.3f} ms, p95 "
            f"{1e3 * _p95(steps):.3f} ms; label p95 over {len(labels_t)} "
            f"calls ({int(len(labels_t) * 0.05)} beyond it)")
    run = Run(workload, conf, cell.mix, batch, drv.calls, drv.label_first,
              tr, peaks, n_steps, engine_counts, dropped)
    metrics: Dict[str, dict] = {}
    if not trace:
        values = {
            "setup_s": setup_s,
            "updates_per_s": updates / window_s if updates else None,
            "label_p95_us": 1e6 * _p95(labels_t) if labels_t else None,
        }
        for m in cell.end_to_end:
            v = values.get(m["name"])
            if v is None and drv.error is None:
                raise RuntimeError(f"{m['name']} was not measured")
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in cell.per_layer:
            reader = _load_module(root / "bench" / "metrics"
                                  / f"{m['name']}.py")
            v = reader.read(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    checks = {name: {"value": v, "limit": 0} for name, v in counts.items()}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(used),
              "memory_peak_bytes": peak_mem}
    result = {"correct": correct, "attempted": drv.attempted,
              "failed": drv.failed, "metrics": metrics, "device": device}
    if tr is not None:
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        result["breakdown"] = tr["breakdown"]
    result["checks"] = checks
    return result
