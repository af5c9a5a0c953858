"""``slot_counts`` (``kernels/ops.py``, a jitted XLA program): share of its
bandwidth roofline per insert call."""

import roofline  # bench/roofline.py


def least_bytes(rows: int, t: int) -> int:
    """The work's bytes: ``rows * t`` int32 slot ids in and as many int32
    increments out.  Not the capacity-sized histogram the program writes:
    a change that stops writing it moves the share honestly."""
    return rows * t * 4 + rows * t * 4


def read(run):
    return roofline.share(run, "jit_slot_counts",
                           least_bytes(run.batch, run.config["t"]))
