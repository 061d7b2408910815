"""Rescue (``core/laf_dbscan._rescue_and_finish``, Algorithm 3): mean
``laf.postprocess`` span per call, ms."""


def read(rec):
    s = rec["spans"].get("laf.postprocess")
    return 1e3 * sum(s) / len(s) if s else None
