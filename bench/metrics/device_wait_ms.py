"""Device round trips: whole time of the ``soa.device.fetch`` spans under
``soa.insert`` (the host waiting for the hash keys, the occupancy delta
and the support to come back) per insert call."""

import phases  # bench/phases.py

FETCH, INSERT = "soa.device.fetch", "soa.insert"


def read(run):
    tr = phases.trace(run)
    if tr is None:
        return None
    fetches = [st["seconds"] for path, st in tr["engine_paths"].items()
               if path.startswith(INSERT + "/") and path.endswith("/" + FETCH)]
    return phases.ms_per(run, sum(fetches), INSERT) if fetches else None
