"""Sweep: the bitmap build's share of its roofline, in %: its least time
(``bench/kernels/hamming_filter.py``: the larger of the least bytes over
the HBM bandwidth and the band verify's flops over the bfloat16 peak)
over the device time per call of the slab sweep's filter ops (those
inside its ``while`` loop: the call site whose work is counted)."""

import registry


def read(rec):
    dev = rec.get("device")
    if not dev or not dev["kernels_within"].get("hamming_filter") or not rec["n_exec"]:
        return None
    t = dev["kernels_within"]["hamming_filter"] / rec["calls"]
    k = registry.load_module("kernels", "hamming_filter")
    pk = rec["peaks"]
    least = max(k.least_bytes(rec["n"], rec["d"], rec["n_exec"], rec["n_bits"])
                / pk["hbm_bytes_per_s"],
                k.least_flops(rec["d"], rec.get("band_pairs", 0)) / pk["bf16_flops"])
    return 100.0 * least / t
