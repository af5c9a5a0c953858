"""Share of a program's roofline: the least time the chip could take for
the work, over the device time the trace gives the program.

The least time is the larger of operations over peak operations per
second and bytes over peak bytes per second.  The index's programs do
int32 work, for which the chip has no published peak, so their bound is
their bytes alone, over the HBM bandwidth of ``peaks.json``."""


def share(run, program: str, bytes_per_call: float):
    """Percent of the bandwidth roofline, or None where the trace holds no
    execution of ``program`` (a CPU run, or the program off the path)."""
    if run.trace is None or run.peaks is None:
        return None
    p = run.trace["programs"].get(program)
    if not p or p["count"] == 0 or p["seconds"] <= 0:
        return None
    least = bytes_per_call / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least * p["count"] / p["seconds"]
