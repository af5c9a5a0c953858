"""Event replay, expire side: self time of ``soa.expire.replay``
(``core/soa.py`` ``_replay_deletes``: each point's journal record and
member sets, released borders, demotion cascades) per expire call."""

import phases  # bench/phases.py


def read(run):
    return phases.phase_ms(run, "soa.expire.replay", "soa.expire")
