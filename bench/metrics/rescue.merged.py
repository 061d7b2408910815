"""Rescue: points Algorithm 3 assigned to a cluster per call (counter
``laf.rescue.merged``, counted by ``post_processing``)."""


def read(rec):
    if not rec["calls"] or "laf.rescue.merged" not in rec["counters"]:
        return None
    return rec["counters"]["laf.rescue.merged"] / rec["calls"]
