"""Cluster formation: the host assembly of labels after the cluster
pass's single ``device_get``, and the release of the slab
(``laf.assemble``, one span per call), summed per call, ms."""


def read(rec):
    s = rec["spans"].get("laf.assemble")
    return 1e3 * sum(s) / rec["calls"] if s and rec["calls"] else None
