"""Sweep (``index/sweep`` -> ``kernels/hamming_filter``): device time of
the filter kernel's trace events per call, ms."""


def read(rec):
    dev = rec.get("device")
    if not dev or not dev["kernel_found"].get("hamming_filter"):
        return None
    return 1e3 * dev["kernels"]["hamming_filter"] / rec["calls"]
