"""Connectivity epoch, the merge: whole time of ``soa.epoch.merge`` per
merge (``core/soa.py`` ``_merge_epoch``: the first read after inserts
folds their new core rows into the cached components on the host)."""

import phases  # bench/phases.py


def read(run):
    return phases.phase_ms(run, "soa.epoch.merge", "soa.epoch.merge",
                           total=True)
