"""Cluster formation (``kernels/label_prop``): device time of the
label-prop and col-reduce kernels' trace events per call, ms."""


def read(rec):
    dev = rec.get("device")
    if not dev or not dev["kernel_found"].get("label_prop"):
        return None
    return 1e3 * dev["kernels"]["label_prop"] / rec["calls"]
