"""Rescue: Algorithm 3's merges and the label compaction
(``laf.rescue.merge``, one span per call), summed per call, ms."""


def read(rec):
    s = rec["spans"].get("laf.rescue.merge")
    return 1e3 * sum(s) / rec["calls"] if s and rec["calls"] else None
