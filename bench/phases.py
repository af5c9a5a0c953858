"""What the readers of the engine's phases share: the ``soa.*`` spans of
the traced window (``trace_reduce.reduce``'s ``engine`` by name and
``engine_paths`` by path), trusted only where the engine's tracer
dropped none."""


def trace(run):
    """The run's reduced trace, or None where the run was not traced or
    the engine's tracer dropped spans."""
    return None if run.trace is None or run.dropped else run.trace


def ms_per(run, seconds: float, per: str):
    """``seconds`` in milliseconds per span ``per`` in the window."""
    tr = trace(run)
    n = tr["engine"].get(per, {}).get("count", 0) if tr else 0
    return 1e3 * seconds / n if n else None


def phase_ms(run, name: str, per: str, total: bool = False):
    """Self time (with ``total``, whole time) of the span ``name`` per span
    ``per``; None where either is absent."""
    tr = trace(run)
    if tr is None or name not in tr["engine"]:
        return None
    st = tr["engine"][name]
    return ms_per(run, st["seconds" if total else "self_seconds"], per)
