"""Kernel microbenchmarks: ``name,us_per_call,derived`` CSV rows.

Times the jnp oracles on the default backend and derives each kernel's
v5e roofline bound (``tpu_bound_us``) from its analytic FLOPs/bytes and
the peaks table in :mod:`repro.launch.roofline`.  On a CPU the times
measure XLA:CPU, not a TPU."""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.synthetic import sample_uniform_sphere
from repro.launch.roofline import TARGET_DEVICE_KIND, peaks

_V5E = peaks(TARGET_DEVICE_KIND)
PEAK_FLOPS = _V5E["flops"]
HBM_BW = _V5E["hbm_bw"]


def _time(fn, *args, iters=5):
    fn(*args)  # compile
    t0 = time.time()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.time() - t0) / iters * 1e6  # us


def run():
    rows = []
    rng = np.random.default_rng(0)

    # range_count: 4096 queries x 65536 db x 768-d
    q = jnp.asarray(sample_uniform_sphere(rng, 1024, 768))
    db = jnp.asarray(sample_uniform_sphere(rng, 16384, 768))
    from repro.core.range_query import range_counts

    us = _time(lambda a, b: range_counts(a, b, 0.5), q, db)
    flops = 2 * 1024 * 16384 * 768
    bound_us = max(flops / PEAK_FLOPS, (q.nbytes + db.nbytes + 1024 * 4) / HBM_BW) * 1e6
    rows.append(("range_count_1024x16384x768", us, f"tpu_bound_us={bound_us:.1f}"))

    # rmi_mlp: batch 4096 through the paper's 4-layer net
    from repro.core.cardinality.rmi import init_mlp, mlp_apply

    params = init_mlp(jax.random.PRNGKey(0), 769, (512, 512, 256, 128))
    x = jax.random.normal(jax.random.PRNGKey(1), (4096, 769))
    us = _time(lambda p, xx: mlp_apply(p, xx), params, x)
    flops = 2 * 4096 * (769 * 512 + 512 * 512 + 512 * 256 + 256 * 128 + 128)
    rows.append(("rmi_mlp_4096x769", us, f"tpu_bound_us={flops / PEAK_FLOPS * 1e6:.1f}"))

    # label_prop round: 8192 nodes
    from repro.core.range_query import pack_bitmap
    from repro.kernels.label_prop.ref import label_prop_round_ref

    adj = rng.random((2048, 2048)) < 0.005
    adj |= adj.T
    bm = jnp.asarray(pack_bitmap(adj))
    labels = jnp.arange(2048, dtype=jnp.int32)
    big = jnp.int32(np.iinfo(np.int32).max)
    us = _time(lambda l, b: label_prop_round_ref(l, b, big), labels, bm)
    byts = bm.nbytes * 32 + 2048 * 4 * 2
    rows.append(("label_prop_2048", us, f"tpu_bound_us={byts / HBM_BW * 1e6:.1f}"))

    # embedding_bag: 8192 bags of 32 from a 1M-row table
    from repro.kernels.embedding_bag.ref import embedding_bag_ref

    table = jax.random.normal(jax.random.PRNGKey(2), (100000, 64))
    ids = jnp.asarray(rng.integers(0, 100000, (8192, 32)).astype(np.int32))
    us = _time(lambda t, i: embedding_bag_ref(t, i), table, ids)
    byts = 8192 * 32 * 64 * 4 + 8192 * 64 * 4
    rows.append(("embedding_bag_8192x32x64", us, f"tpu_bound_us={byts / HBM_BW * 1e6:.1f}"))

    # flash attention forward: 4x8 heads x 1024 x 64
    from repro.kernels.flash_attention.ref import attention_ref

    qk = jax.random.normal(jax.random.PRNGKey(3), (4, 8, 1024, 64))
    us = _time(lambda a: attention_ref(a, a, a, causal=True), qk)
    flops = 4 * 8 * (2 * 1024 * 1024 * 64 * 2) / 2  # causal half
    rows.append(("flash_attn_4x8x1024x64", us, f"tpu_bound_us={flops / PEAK_FLOPS * 1e6:.1f}"))
    return rows


def summarize(rows):
    return "\n".join(f"{n},{us:.1f},{d}" for n, us, d in rows)
