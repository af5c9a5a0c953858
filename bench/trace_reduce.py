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
    window.

Host and device events share one clock in the trace, so the time in
which the device ran nothing is split among the ``bench.*`` spans (other
than the window) that cover it: what the host was doing while the device
waited.  A trace with no device plane (the CPU) reads as zero busy time.
"""

from __future__ import annotations

import bisect
import glob
import gzip
import os
import re
from typing import Dict, List, Tuple

WINDOW_SPAN = "bench.window"
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


def _top(d: Dict[str, float], n: int = 10) -> List[list]:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]


def reduce(path: str, n_devices: int = 1) -> dict:
    """Busy and window seconds, per-program device time and count, and the
    breakdown the result line carries (top device operations, idle time
    by host span)."""
    pd = _load(path)
    spans: List[Tuple[str, int, int]] = []
    dev_ops: Dict[str, List[Tuple[int, int, str]]] = {}
    modules: List[Tuple[str, int, int]] = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        s = int(ev.start_ns)
                        spans.append((ev.name, s, s + int(ev.duration_ns)))
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

    # idle time by what the host was doing: each gap is split among the
    # (sequential) bench spans that cover it, the rest is outside them
    host = sorted((s, e, name) for name, s, e in spans
                  if name != WINDOW_SPAN)
    ends = [e for _, e, _ in host]
    idle: Dict[str, float] = {}
    for gs, ge in gaps:
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
    return {
        "busy_s": busy_ns / 1e9 / max(n_devices, 1),
        "window_s": window_s,
        "programs": programs,
        "breakdown": {"device_ops": _top(op_time), "idle_gaps": _top(idle)},
    }
