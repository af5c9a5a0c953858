"""Engine insert layer: mean host time of one ``insert_batch`` call in the
window (``SoAIndex.insert_batch`` -> ``core/soa.py`` ``add_batch``: hash,
slot resolution, device occupancy and support, event replay)."""


def read(run):
    calls = run.calls.get("insert")
    return 1e3 * sum(calls) / len(calls) if calls else None
