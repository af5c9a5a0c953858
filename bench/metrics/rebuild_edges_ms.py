"""Connectivity epoch, host side: self time of ``soa.rebuild.edges`` per
rebuild (on ``soa-device``: the core mask, the renumbering of slots by
occupancy and the copy in; on ``soa``: the chain edges)."""

import phases  # bench/phases.py


def read(run):
    return phases.phase_ms(run, "soa.rebuild.edges", "soa.rebuild")
