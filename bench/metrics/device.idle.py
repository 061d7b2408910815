"""Device: share of the traced window in which no op ran on the chip,
``1 - busy_s / window_s``, in %."""


def read(rec):
    dev = rec.get("device")
    if not dev or not dev["chips_traced"] or dev["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - dev["busy_s"] / dev["window_s"])
