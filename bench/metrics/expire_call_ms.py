"""Engine expire layer: mean host time of one ``delete_batch`` call in the
window (``core/soa.py`` ``delete_batch``, host only)."""


def read(run):
    calls = run.calls.get("expire")
    return 1e3 * sum(calls) / len(calls) if calls else None
