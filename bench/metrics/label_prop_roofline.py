"""Cluster formation: the label-prop kernels' share of their memory
roofline, in %: the slab read once (``bench/kernels/label_prop.py``) over
the chip's HBM bandwidth, over their device time per call."""

import registry


def read(rec):
    dev = rec.get("device")
    if not dev or not dev["kernels_within"].get("label_prop") or not rec["n_exec"]:
        return None
    t = dev["kernels_within"]["label_prop"] / rec["calls"]
    b = registry.load_module("kernels", "label_prop").least_bytes(
        rec["n"], rec["d"], rec["n_exec"], rec["n_bits"])
    return 100.0 * b / rec["peaks"]["hbm_bytes_per_s"] / t
