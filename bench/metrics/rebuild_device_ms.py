"""Connectivity epoch, the components: whole time of ``soa.rebuild.sv``
per rebuild (on ``soa-device``: the ``core_components`` program's
dispatch, its run and the fetch of its answer)."""

import phases  # bench/phases.py


def read(run):
    return phases.phase_ms(run, "soa.rebuild.sv", "soa.rebuild", total=True)
