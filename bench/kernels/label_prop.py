"""Least bytes of cluster formation (``label_prop`` and ``col_reduce``).

The core test, the components and the border owners all read the packed
adjacency slab, one bit per (executed row, point); the least any
implementation moves is that slab read once.
"""

# the tpu_custom_call ops of ``_label_prop_kernel`` and ``_col_reduce_kernel``
EVENTS = ("label_prop_rect_pallas", "col_reduce_pallas")


def least_bytes(n: int, d: int, n_exec: int, n_bits: int) -> int:
    return 4 * n_exec * (-(-n // 32))
