"""Slot resolution: self time of ``soa.insert.resolve_slots``
(``core/soa.py`` ``_resolve_slots``: keys to slot ids, opening directory
entries) per insert call."""

import phases  # bench/phases.py


def read(run):
    return phases.phase_ms(run, "soa.insert.resolve_slots", "soa.insert")
