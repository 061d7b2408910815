"""Size a corpus for one chip and for four by compiling, not running.

    JAX_PLATFORMS=cpu python3 bench/sizing.py --d 768 --exec-share 0.65 \\
        --n 152185 240000 260000 300000 400000 480000 520000

For each n this compiles, for a described TPU v5e (``v5e:2x2``, nothing
attached), the two programs of one ``cluster_laf_dbscan`` call that hold
the packed adjacency slab: the one-launch bitmap sweep and the packed
cluster program on one chip, and their sharded plane forms on four
chips.  It prints, per program, the bytes the compiler plans per device
(arguments + outputs + temporaries - aliases) or the compiler's refusal.
``exec-share`` is the share of the corpus the RMI executes (the slab has
that many rows).  Where a compile passes, the call still needs the
database and its signatures resident beside it: that is added as
``resident``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

CHUNK, CPL, WORDS = 256, 8, 16
HBM = 16 * 10**9


def _bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def _try(fn):
    try:
        return _bytes(fn())
    except Exception as e:  # the compiler's refusal is the finding
        return f"refused: {type(e).__name__}: {str(e).splitlines()[0][:160]}"


def size(n: int, d: int, share: float, topo) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

    from repro.distributed.index_plane import (
        _build_cluster_plane_fn, _build_sweep_plane_fn, shard_plan)
    from repro.index.sweep import _bitmap_launch_donated
    from repro.kernels.label_prop.ops import _packed_cluster_jit

    one = SingleDeviceSharding(topo.devices[0])
    db_rows = -(-n // 256) * 256
    words = db_rows // 32
    launch = CHUNK * CPL
    r_exec = -(-int(share * n) // launch) * launch

    def s(shape, dtype, sh=one):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    out = {"n": n, "d": d, "exec_rows": r_exec,
           "slab_bytes": 4 * r_exec * words,
           "resident": db_rows * (4 * d + 4 * WORDS)}
    out["one_chip.sweep"] = _try(lambda: _bitmap_launch_donated.trace(
        s((r_exec,), jnp.int32), s((r_exec, words), jnp.uint32),
        s((r_exec // CHUNK, 3), jnp.int32), s((), jnp.int32),
        s((launch, d), jnp.float32), s((launch, WORDS), jnp.uint32),
        s((db_rows, d), jnp.float32), s((db_rows, WORDS), jnp.uint32),
        s((1,), jnp.float32), s((2,), jnp.int32),
        chunk=CHUNK, q_tile=128, db_tile=256, interpret=False,
    ).lower().compile())
    out["one_chip.cluster"] = _try(lambda: jax.jit(
        lambda b, r, t: _packed_cluster_jit(b, r, t, n=n, max_iters=64, row_tile=256,
                                            word_tile=128, interpret=False)
    ).lower(s((r_exec, words), jnp.uint32), s((r_exec,), jnp.int32),
            s((), jnp.int32)).compile())

    mesh = Mesh(np.asarray(topo.devices[:4]), ("data",))
    plan = shard_plan(mesh, n, tile=4096)
    rep = NamedSharding(mesh, P())
    rows = NamedSharding(mesh, P("data", None))
    sweep = _build_sweep_plane_fn(mesh, ("data",), "bitmap", CHUNK, 128, 256, False, 2)
    out["four_chips.sweep"] = _try(lambda: sweep.lower(
        s((launch, d), jnp.float32, rep), s((launch, WORDS), jnp.uint32, rep),
        s((plan.n_padded, d), jnp.float32, rows),
        s((plan.n_padded, WORDS), jnp.uint32, rows),
        s((1,), jnp.float32, rep), s((2,), jnp.int32, rep)).compile())
    clus = _build_cluster_plane_fn(mesh, ("data",), n, 64, 256, 128, False, False)
    out["four_chips.cluster"] = _try(lambda: clus.lower(
        s((r_exec, plan.n_padded // 32), jnp.uint32, NamedSharding(mesh, P(None, "data"))),
        s((r_exec,), jnp.int32, rep), s((1,), jnp.int32, rep)).compile())
    out["four_chips.slab_bytes_per_chip"] = 4 * r_exec * plan.n_padded // 32 // 4
    out["four_chips.resident_per_chip"] = plan.n_padded * (4 * d + 4 * WORDS) // 4
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--n", type=int, nargs="+", required=True)
    ap.add_argument("--d", type=int, default=768)
    ap.add_argument("--exec-share", type=float, default=0.65)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    for n in args.n:
        print(json.dumps(size(n, args.d, args.exec_share, topo)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
