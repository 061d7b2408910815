"""Sweep: host-to-device copies of the database-sized operands
(``laf.upload``, one span per copy, synced on the device array),
summed per call, ms."""


def read(rec):
    s = rec["spans"].get("laf.upload")
    return 1e3 * sum(s) / rec["calls"] if s and rec["calls"] else None
