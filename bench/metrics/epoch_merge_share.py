"""Connectivity epoch, how often the merge serves a read: merges over
merges plus full rebuilds in the traced window, from the counts of the
spans ``soa.epoch.merge`` and ``soa.rebuild``."""

import phases  # bench/phases.py


def read(run):
    tr = phases.trace(run)
    if tr is None or "soa.epoch.merge" not in tr["engine"]:
        return None
    merges = tr["engine"]["soa.epoch.merge"]["count"]
    rebuilds = tr["engine"].get("soa.rebuild", {}).get("count", 0)
    return merges / (merges + rebuilds)
