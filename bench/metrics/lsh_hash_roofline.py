"""``lsh_hash`` (``kernels/ops.py``, the Pallas kernel on a TPU): share of
its bandwidth roofline per insert call."""

import roofline  # bench/roofline.py


def least_bytes(rows: int, d: int, t: int) -> int:
    """The work's bytes: ``rows`` f32 points of ``d`` coordinates in, and
    ``t`` keys of two int32 words each out.  ``rows`` is the real batch,
    not the padded rows the program is handed, so a change that drops the
    padding moves the share and it cannot pass 100%."""
    return rows * d * 4 + rows * t * 8


def read(run):
    c = run.config
    return roofline.share(run, "jit_lsh_hash",
                           least_bytes(run.batch, c["d"], c["t"]))
