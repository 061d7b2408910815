"""Rescue: points rescued per call (counter ``laf.rescued``)."""


def read(rec):
    if not rec["calls"] or "laf.rescued" not in rec["counters"]:
        return None
    return rec["counters"]["laf.rescued"] / rec["calls"]
