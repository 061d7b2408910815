"""Rescue: (executed row, rescued point) hits registered in the
partial-neighbour map per call (counter ``laf.rescue.pairs``)."""


def read(rec):
    if not rec["calls"] or "laf.rescue.pairs" not in rec["counters"]:
        return None
    return rec["counters"]["laf.rescue.pairs"] / rec["calls"]
