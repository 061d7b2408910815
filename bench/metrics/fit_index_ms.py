"""Index fit (``index/random_projection.fit``): mean ``laf.fit_index``
span per call, ms."""


def read(rec):
    s = rec["spans"].get("laf.fit_index")
    return 1e3 * sum(s) / len(s) if s else None
