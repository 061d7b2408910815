"""Least work of the bitmap sweep (``hamming_filter`` bitmap build).

Any implementation reads the executed queries and the database once
(f32 rows and packed signatures) and writes the packed adjacency slab,
one bit per (executed row, point), once; and it verifies, with an f32
dot of 2 d flops, every pair whose Hamming distance falls inside the
band (counted by the reference as ``band_pairs``).  The Hamming pass
itself (XOR and popcount on the VPU) has no published peak.  The least
time is the larger of the bytes over the HBM bandwidth and the verify's
flops over the bfloat16 peak (the loosest: an f32 dot runs slower).
"""

# the tpu_custom_call op of ``_filter_kernel``: every build (the slab
# sweep, the rescue's column-subset sweep, the occupancy stats) for the
# layer's time, ``sweep.kernel_ms``
EVENTS = ("hamming_filter_pallas",)
# the slab sweep alone runs inside a ``while`` loop over query tiles;
# the roofline's time is that call site's, whose work is counted here
WITHIN = ("while",)


def least_bytes(n: int, d: int, n_exec: int, n_bits: int) -> int:
    sig = 4 * (n_bits // 32)
    slab = 4 * n_exec * (-(-n // 32))
    return n_exec * (4 * d + sig) + n * (4 * d + sig) + slab


def least_flops(d: int, band_pairs: int) -> int:
    return 2 * d * band_pairs
