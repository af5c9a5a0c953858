"""Event replay, insert side: self time of ``soa.insert.events``
(``core/soa.py`` ``_apply_insert_events``: the grab and scan replay over
orphan and core rows) per insert call."""

import phases  # bench/phases.py


def read(run):
    return phases.phase_ms(run, "soa.insert.events", "soa.insert")
