"""RMI predict (``core/cardinality``): mean ``laf.predict`` span per call, ms."""


def read(rec):
    s = rec["spans"].get("laf.predict")
    return 1e3 * sum(s) / len(s) if s else None
