"""Event replay, insert side: rows the grab and scan replays examined
(``core/soa.py`` ``_apply_insert_events``: its counters
``soa.replay.grab_rows``, the orphan candidates, and
``soa.replay.scan_rows``, the core rows of the border scan's pool; deltas
over the traced window) per step of the mix."""

import phases  # bench/phases.py

COUNTERS = ("soa.replay.grab_rows", "soa.replay.scan_rows")


def read(run):
    c = run.counters
    if (phases.trace(run) is None or not run.steps
            or not any(k in c for k in COUNTERS)):
        return None
    return sum(c.get(k, 0) for k in COUNTERS) / run.steps
