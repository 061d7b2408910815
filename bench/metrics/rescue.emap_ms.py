"""Rescue: the host loop that fills the partial-neighbour map
(``laf.rescue.emap``, one span per block of executed rows), summed per
call, ms."""


def read(rec):
    s = rec["spans"].get("laf.rescue.emap")
    return 1e3 * sum(s) / rec["calls"] if s and rec["calls"] else None
