"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's numbers.

What the planes are, as JAX 0.9 writes them for one TPU v5e host:

  * a device is a plane named ``/device:TPU:<n>``.  Its lines ``XLA Ops``
    and ``Async XLA Ops`` hold one event per operation the device ran,
    named by the operation's HLO text (``%copy.2 = s32[...] copy(...)``);
    the union of those intervals is the device's busy time, and an
    operation is named ``<module>:<op>`` after the program execution that
    contains it.  Its line ``XLA Modules`` holds
    one event per program execution, named ``<module>(<id>)``: a jitted
    function ``f`` runs as the module ``jit_f``, so the index's programs
    are recognised by name as ``jit_lsh_hash`` (the Pallas hash kernel and
    the few ops around it), ``jit_slot_counts`` and
    ``jit_bucket_core_stats``;
  * the host is the plane ``/host:CPU``.  The benchmark's own spans
    (``jax.profiler.TraceAnnotation``) sit on the line of the thread that
    ran them, named ``bench.<op>``; ``bench.window`` spans the measured
    window.  With the engine's observability on, ``core/soa.py``'s phase
    spans sit there too, named ``soa.<phase>`` (up to any ``#``, after
    which a profiler may keep the span's attributes), nested as the calls
    nest: ``soa.insert`` over ``soa.insert.hash`` over
    ``soa.device.fetch``.

Host and device events share one clock in the trace, so the time in
which the device ran nothing is split among what the host was doing
while the device waited: the innermost ``soa.*`` span that covers it,
else the ``bench.*`` span (other than the window).  A trace with no
device plane (the CPU) reads as zero busy time.
"""

from __future__ import annotations

import bisect
import glob
import gzip
import os
import re
from typing import Dict, List, Tuple

WINDOW_SPAN = "bench.window"
ENGINE_PREFIX = "soa."
IDLE_REST = "all other spans"
_SUFFIX = re.compile(r"\(\d+\)$")


def find_trace(log_dir: str) -> str:
    """The one ``.xplane.pb`` that a trace into ``log_dir`` wrote."""
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise FileNotFoundError(f"{len(found)} traces under {log_dir}")
    return found[0]


def _load(path: str):
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def _union(iv: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(iv, lo: int, hi: int):
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


def _top(d: Dict[str, float], n: int = 10, rest: str = "") -> List[list]:
    """The ``n`` largest entries; with ``rest``, past ``n`` entries the
    last is ``rest`` with the sum of all but the ``n - 1`` largest, so the
    list still sums to the whole."""
    ranked = [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])]
    if rest and len(ranked) > n:
        return ranked[:n - 1] + [[rest, sum(v for _, v in ranked[n - 1:])]]
    return ranked[:n]


def _nest(events: List[Tuple[int, int, str]]):
    """The events of one thread line as a tree: each with its parent's
    index (-1 at the top), in order of start, the longer first; an event
    that outlasts its parent is cut at the parent's end."""
    out: List[Tuple[int, int, str, int]] = []
    stack: List[int] = []
    for s, e, name in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        while stack and out[stack[-1]][1] <= s:
            stack.pop()
        parent = stack[-1] if stack else -1
        if parent >= 0:
            e = min(e, out[parent][1])
        stack.append(len(out))
        out.append((s, e, name, parent))
    return out


def _phases(lines: List[List[Tuple[int, int, str]]]):
    """Count, total and self seconds of the engine's spans by name and by
    path (``soa.insert/soa.insert.hash/soa.device.fetch``), and the
    pieces of time each span covers with none of its children in it,
    named by the span: the innermost span at each instant."""
    by_name: Dict[str, Dict[str, float]] = {}
    by_path: Dict[str, Dict[str, float]] = {}
    pieces: List[Tuple[int, int, str]] = []
    for events in lines:
        tree = _nest(events)
        kids: List[List[int]] = [[] for _ in tree]
        paths: List[str] = []
        for i, (_, _, name, parent) in enumerate(tree):
            if parent >= 0:
                kids[parent].append(i)
            paths.append(name if parent < 0 else f"{paths[parent]}/{name}")
        for i, (s, e, name, _) in enumerate(tree):
            at, own = s, 0
            for c in kids[i]:
                cs, ce = tree[c][0], tree[c][1]
                if cs > at:
                    pieces.append((at, cs, name))
                    own += cs - at
                at = max(at, ce)
            if e > at:
                pieces.append((at, e, name))
                own += e - at
            for table, key in ((by_name, name), (by_path, paths[i])):
                st = table.setdefault(key, {"count": 0, "seconds": 0.0,
                                            "self_seconds": 0.0})
                st["count"] += 1
                st["seconds"] += (e - s) / 1e9
                st["self_seconds"] += own / 1e9
    # pieces of different lines could overlap: each instant goes to the
    # first piece that reaches it
    flat: List[Tuple[int, int, str]] = []
    for s, e, name in sorted(pieces):
        s = max(s, flat[-1][1]) if flat else s
        if e > s:
            flat.append((s, e, name))
    return by_name, by_path, flat


def _split_idle(gaps: List[Tuple[int, int]],
                inner: List[Tuple[int, int, str]],
                host: List[Tuple[int, int, str]]) -> Dict[str, float]:
    """Idle seconds by what the host was doing: each gap goes to the
    innermost engine span at each instant (``inner``, pieces that do not
    overlap), and what no engine span covers is split among the
    (sequential) bench spans that cover it (``host``, sorted), the rest is
    outside them."""
    ends = [e for _, e, _ in host]
    inner_ends = [e for _, e, _ in inner]
    idle: Dict[str, float] = {}

    def by_bench_span(gs: int, ge: int) -> None:
        rest = ge - gs
        for s, e, name in host[bisect.bisect_right(ends, gs):]:
            if s >= ge:
                break
            ov = min(e, ge) - max(s, gs)
            idle[name] = idle.get(name, 0.0) + ov / 1e9
            rest -= ov
        if rest > 0:
            idle["host outside bench spans"] = (
                idle.get("host outside bench spans", 0.0) + rest / 1e9)

    for gs, ge in gaps:
        at = gs
        for s, e, name in inner[bisect.bisect_right(inner_ends, gs):]:
            if s >= ge:
                break
            if s > at:
                by_bench_span(at, s)
            at = max(s, gs)
            idle[name] = idle.get(name, 0.0) + (min(e, ge) - at) / 1e9
            at = min(e, ge)
        if ge > at:
            by_bench_span(at, ge)
    return idle


def reduce(path: str, n_devices: int = 1) -> dict:
    """Busy and window seconds, per-program device time and count, the
    engine's phases in the window, idle seconds by host span, and the
    breakdown the result line carries (top device operations, the largest
    idle shares by host span and the rest summed)."""
    pd = _load(path)
    spans: List[Tuple[str, int, int]] = []
    soa: List[List[Tuple[int, int, str]]] = []
    dev_ops: Dict[str, List[Tuple[int, int, str]]] = {}
    modules: List[Tuple[str, int, int]] = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                soa.append([])
                for ev in line.events:
                    s = int(ev.start_ns)
                    if ev.name.startswith("bench."):
                        spans.append((ev.name, s, s + int(ev.duration_ns)))
                    elif ev.name.startswith(ENGINE_PREFIX):
                        soa[-1].append((s, s + int(ev.duration_ns),
                                        ev.name.split("#", 1)[0]))
        elif plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name in ("XLA Ops", "Async XLA Ops"):
                    ops = dev_ops.setdefault(plane.name, [])
                    for ev in line.events:
                        s = int(ev.start_ns)
                        name = ev.name.split(" = ", 1)[0].lstrip("%")
                        ops.append((s, s + int(ev.duration_ns), name))
                elif line.name == "XLA Modules":
                    for ev in line.events:
                        s = int(ev.start_ns)
                        modules.append((_SUFFIX.sub("", ev.name), s,
                                        s + int(ev.duration_ns)))
    win = [(s, e) for name, s, e in spans if name == WINDOW_SPAN]
    if win:
        lo, hi = win[0]
    elif spans:
        lo, hi = min(s for _, s, _ in spans), max(e for _, _, e in spans)
    else:
        raise ValueError(f"no bench.* spans in {path}")
    window_s = (hi - lo) / 1e9

    busy_ns = 0
    gaps: List[Tuple[int, int]] = []
    op_time: Dict[str, float] = {}
    modules.sort(key=lambda m: m[1])
    starts = [s for _, s, _ in modules]
    for ops in dev_ops.values():
        iv = _union(_clip([(s, e) for s, e, _ in ops], lo, hi))
        busy_ns += sum(e - s for s, e in iv)
        edges = [lo] + [x for se in iv for x in se] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
        for s, e, name in ops:
            if e > lo and s < hi:
                i = bisect.bisect_right(starts, s) - 1
                if i >= 0 and s < modules[i][2]:
                    name = f"{modules[i][0]}:{name}"
                op_time[name] = op_time.get(name, 0.0) + (
                    min(e, hi) - max(s, lo)) / 1e9
    programs: Dict[str, Dict[str, float]] = {}
    for name, s, e in modules:
        if lo <= s < hi:
            p = programs.setdefault(name, {"seconds": 0.0, "count": 0})
            p["seconds"] += (e - s) / 1e9
            p["count"] += 1

    engine, engine_paths, inner = _phases(
        [[ev for ev in line if lo <= ev[0] and ev[1] <= hi] for line in soa])

    host = sorted((s, e, name) for name, s, e in spans
                  if name != WINDOW_SPAN)
    idle = _split_idle(gaps, inner, host)
    return {
        "busy_s": busy_ns / 1e9 / max(n_devices, 1),
        "window_s": window_s,
        "programs": programs,
        "engine": engine,
        "engine_paths": engine_paths,
        "idle": idle,
        "breakdown": {"device_ops": _top(op_time),
                      "idle_gaps": _top(idle, rest=IDLE_REST)},
    }
