"""Rescue: passes of the host loop that fills the partial-neighbour map,
one per (block of executed rows, rescued point hit), per call (counter
``laf.rescue.visits``)."""


def read(rec):
    if not rec["calls"] or "laf.rescue.visits" not in rec["counters"]:
        return None
    return rec["counters"]["laf.rescue.visits"] / rec["calls"]
