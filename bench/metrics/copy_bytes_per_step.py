"""Host-device copies: bytes the engine copied to and from the device
(its counters ``soa.h2d_bytes`` and ``soa.d2h_bytes``, deltas over the
traced window) per step of the mix."""

import phases  # bench/phases.py

COUNTERS = ("soa.h2d_bytes", "soa.d2h_bytes")


def read(run):
    c = run.counters
    if (phases.trace(run) is None or not run.steps
            or not any(k in c for k in COUNTERS)):
        return None
    return sum(c.get(k, 0) for k in COUNTERS) / run.steps
