"""Sweep: bytes copied from host to device in database-sized operands
per call (counter ``index.upload.bytes``), GB."""


def read(rec):
    if not rec["calls"] or "index.upload.bytes" not in rec["counters"]:
        return None
    return rec["counters"]["index.upload.bytes"] / rec["calls"] / 1e9
