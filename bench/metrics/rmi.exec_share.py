"""RMI predict: share of the corpus the skip rule executes,
``laf.predicted_core`` over n, in %."""


def read(rec):
    if not rec["calls"] or "laf.predicted_core" not in rec["counters"]:
        return None
    return 100.0 * rec["counters"]["laf.predicted_core"] / (rec["calls"] * rec["n"])
