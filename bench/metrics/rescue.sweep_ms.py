"""Rescue: the subset sweep (``laf.rescue.sweep``, one span per block of
executed rows around ``query_hits_subset``, synced), summed per call, ms."""


def read(rec):
    s = rec["spans"].get("laf.rescue.sweep")
    return 1e3 * sum(s) / rec["calls"] if s and rec["calls"] else None
