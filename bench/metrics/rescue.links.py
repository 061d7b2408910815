"""Rescue: distinct (pre-merge cluster, rescued point) incidences that
Algorithm 3 reads per call, the size of the merge's input (counter
``laf.rescue.links``, counted by ``post_processing_incidence``)."""


def read(rec):
    if not rec["calls"] or "laf.rescue.links" not in rec["counters"]:
        return None
    return rec["counters"]["laf.rescue.links"] / rec["calls"]
