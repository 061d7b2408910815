"""Per-kernel validation: shape/dtype sweeps, interpret=True vs the
pure-jnp ref.py oracles (the spec's kernel acceptance gate)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data.synthetic import sample_uniform_sphere

# ---------------------------------------------------------------------------
# range_count
# ---------------------------------------------------------------------------
from repro.kernels.range_count.ops import range_count, range_count_bitmap
from repro.kernels.range_count.ref import range_count_bitmap_ref, range_count_ref


@pytest.mark.parametrize("nq,nd,d", [(64, 128, 32), (100, 300, 64), (33, 1025, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("eps", [0.3, 0.7, 1.2])
def test_range_count_sweep(nq, nd, d, dtype, eps):
    rng = np.random.default_rng(nq + nd)
    q = jnp.asarray(sample_uniform_sphere(rng, nq, d), dtype)
    db = jnp.asarray(sample_uniform_sphere(rng, nd, d), dtype)
    got = np.asarray(range_count(q, db, eps, q_tile=32, db_tile=64))
    ref = np.asarray(range_count_ref(q, db, eps))
    np.testing.assert_array_equal(got, ref)


@pytest.mark.parametrize("nq,nd", [(40, 96), (64, 257)])
def test_range_count_bitmap_sweep(nq, nd):
    rng = np.random.default_rng(7)
    q = jnp.asarray(sample_uniform_sphere(rng, nq, 48))
    db = jnp.asarray(sample_uniform_sphere(rng, nd, 48))
    gc, gb = range_count_bitmap(q, db, 0.6, q_tile=32, db_tile=64)
    rc, rb = range_count_bitmap_ref(q, db, 0.6)
    np.testing.assert_array_equal(np.asarray(gc), np.asarray(rc))
    np.testing.assert_array_equal(np.asarray(gb), np.asarray(rb))


def test_range_count_agrees_with_core_engine():
    """Kernel vs the jnp engine used by the clustering core."""
    from repro.core.range_query import range_counts

    rng = np.random.default_rng(11)
    db = jnp.asarray(sample_uniform_sphere(rng, 500, 32))
    got = np.asarray(range_count(db, db, 0.4, q_tile=64, db_tile=128))
    ref = np.asarray(range_counts(db, db, 0.4))
    np.testing.assert_array_equal(got, ref)


# ---------------------------------------------------------------------------
# rmi_mlp
# ---------------------------------------------------------------------------
from repro.core.cardinality.rmi import RMIConfig, init_mlp, init_rmi, mlp_apply
from repro.kernels.rmi_mlp.ops import rmi_mlp_forward, rmi_stage_forward


@pytest.mark.parametrize("d_in", [9, 65, 201, 257, 769])
@pytest.mark.parametrize("batch", [1, 100, 256, 300])
def test_rmi_mlp_sweep(d_in, batch):
    params = init_mlp(jax.random.PRNGKey(d_in), d_in, (512, 512, 256, 128))
    x = jax.random.normal(jax.random.PRNGKey(batch), (batch, d_in))
    got = np.asarray(rmi_mlp_forward(params, x, batch_tile=128))
    ref = np.asarray(mlp_apply(params, x))
    np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)


def test_rmi_mlp_bf16_weights():
    params = init_mlp(jax.random.PRNGKey(0), 33, (512, 512, 256, 128), dtype=jnp.bfloat16)
    x = jax.random.normal(jax.random.PRNGKey(1), (64, 33), jnp.bfloat16)
    got = np.asarray(rmi_mlp_forward(params, x, batch_tile=64))
    ref = np.asarray(mlp_apply([(w.astype(jnp.float32), b.astype(jnp.float32)) for w, b in params],
                               x.astype(jnp.float32)))
    np.testing.assert_allclose(got, ref, rtol=2e-2, atol=2e-2)


def test_rmi_stage_forward_matches_vmap():
    cfg = RMIConfig(input_dim=17)
    rmi = init_rmi(jax.random.PRNGKey(2), cfg)
    x = jax.random.normal(jax.random.PRNGKey(3), (96, 17))
    got = np.asarray(rmi_stage_forward(rmi["stage2"], x, batch_tile=32))
    ref = np.asarray(jax.vmap(lambda p: mlp_apply(p, x))(rmi["stage2"]))
    np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# label_prop
# ---------------------------------------------------------------------------
from repro.core.range_query import pack_bitmap
from repro.core.union_find import connected_components_host, label_propagation
from repro.kernels.label_prop.ops import label_prop_round, label_propagation_pallas
from repro.kernels.label_prop.ref import label_prop_round_ref


@pytest.mark.parametrize("n,p", [(100, 0.05), (300, 0.01), (515, 0.004)])
def test_label_prop_round_sweep(n, p):
    rng = np.random.default_rng(n)
    adj = rng.random((n, n)) < p
    adj = adj | adj.T
    np.fill_diagonal(adj, True)
    bitmap = jnp.asarray(pack_bitmap(adj))
    labels = jnp.asarray(rng.permutation(n).astype(np.int32))
    got = np.asarray(label_prop_round(labels, bitmap, row_tile=64, word_tile=4))
    ref = np.asarray(label_prop_round_ref(labels, bitmap, np.iinfo(np.int32).max))
    np.testing.assert_array_equal(got, ref)


def test_label_prop_full_cc_matches_host():
    rng = np.random.default_rng(5)
    n = 400
    adj = rng.random((n, n)) < 0.008
    adj = adj | adj.T
    np.fill_diagonal(adj, True)
    active = rng.random(n) < 0.8
    adj = adj & active[:, None] & active[None, :]
    bitmap = jnp.asarray(pack_bitmap(adj))
    got = np.asarray(label_propagation_pallas(bitmap, jnp.asarray(active), row_tile=64, word_tile=8))
    host = connected_components_host(n, zip(*np.nonzero(np.triu(adj))), active)
    from repro.core.metrics import adjusted_rand_index

    assert adjusted_rand_index(got[active], host[active]) == 1.0
    jnp_lp = np.asarray(label_propagation(bitmap, jnp.asarray(active)))
    np.testing.assert_array_equal(got, jnp_lp)


def test_label_prop_chain_graph():
    """Worst-case diameter: a path graph must still converge (pointer jumping)."""
    n = 257
    adj = np.zeros((n, n), bool)
    idx = np.arange(n - 1)
    adj[idx, idx + 1] = True
    adj = adj | adj.T
    bitmap = jnp.asarray(pack_bitmap(adj))
    got = np.asarray(
        label_propagation_pallas(bitmap, jnp.ones(n, bool), row_tile=64, word_tile=4)
    )
    assert (got == 0).all()


# ---------------------------------------------------------------------------
# embedding_bag
# ---------------------------------------------------------------------------
from repro.kernels.embedding_bag.ops import embedding_bag
from repro.kernels.embedding_bag.ref import embedding_bag_ref


@pytest.mark.parametrize("v,d,b,l", [(100, 8, 16, 4), (1000, 16, 37, 9), (5000, 64, 24, 39)])
@pytest.mark.parametrize("combiner", ["sum", "mean"])
def test_embedding_bag_sweep(v, d, b, l, combiner):
    rng = np.random.default_rng(v + b)
    table = jnp.asarray(rng.standard_normal((v, d)).astype(np.float32))
    ids = jnp.asarray(rng.integers(-1, v, size=(b, l)).astype(np.int32))
    got = np.asarray(embedding_bag(table, ids, combiner=combiner, batch_tile=8))
    ref = np.asarray(embedding_bag_ref(table, ids, combiner=combiner))
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)


def test_embedding_bag_all_padding_row():
    table = jnp.ones((10, 4), jnp.float32)
    ids = jnp.full((3, 5), -1, jnp.int32)
    got = np.asarray(embedding_bag(table, ids, batch_tile=1))
    np.testing.assert_allclose(got, 0.0)


def test_embedding_bag_bf16_table():
    rng = np.random.default_rng(0)
    table = jnp.asarray(rng.standard_normal((50, 8)), jnp.bfloat16)
    ids = jnp.asarray(rng.integers(0, 50, size=(8, 3)).astype(np.int32))
    got = np.asarray(embedding_bag(table, ids, batch_tile=4))
    ref = np.asarray(embedding_bag_ref(table.astype(jnp.float32), ids))
    np.testing.assert_allclose(got, ref, rtol=2e-2, atol=2e-2)


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref


@pytest.mark.parametrize("s,d,causal", [(128, 32, False), (128, 64, True), (256, 64, True)])
def test_flash_attention_sweep(s, d, causal):
    keys = jax.random.split(jax.random.PRNGKey(s + d), 3)
    q, k, v = (jax.random.normal(kk, (2, 2, s, d)) for kk in keys)
    got = np.asarray(flash_attention(q, k, v, causal=causal, q_block=64, kv_block=64))
    ref = np.asarray(attention_ref(q, k, v, causal=causal))
    np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)


def test_flash_attention_gqa_and_window():
    keys = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(keys[0], (1, 8, 128, 32))
    k = jax.random.normal(keys[1], (1, 2, 128, 32))
    v = jax.random.normal(keys[2], (1, 2, 128, 32))
    got = np.asarray(
        flash_attention(q, k, v, causal=True, window=32, q_block=32, kv_block=32)
    )
    kr, vr = jnp.repeat(k, 4, axis=1), jnp.repeat(v, 4, axis=1)
    ref = np.asarray(attention_ref(q, kr, vr, causal=True, window=32))
    np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)


def test_flash_attention_decode_shape():
    """sq=1 against a long KV (the serve_step shape)."""
    keys = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(keys[0], (2, 4, 1, 64))
    k = jax.random.normal(keys[1], (2, 4, 512, 64))
    v = jax.random.normal(keys[2], (2, 4, 512, 64))
    got = np.asarray(flash_attention(q, k, v, causal=True, q_block=1, kv_block=128))
    ref = np.asarray(attention_ref(q, k, v, causal=True))
    np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)


def test_flash_attention_bf16():
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    q, k, v = (jax.random.normal(kk, (1, 2, 128, 64), jnp.bfloat16) for kk in keys)
    got = np.asarray(flash_attention(q, k, v, causal=True, q_block=64, kv_block=64), np.float32)
    ref = np.asarray(attention_ref(q, k, v, causal=True), np.float32)
    np.testing.assert_allclose(got, ref, rtol=3e-2, atol=3e-2)


# ---------------------------------------------------------------------------
# hamming_filter (dual-threshold band kernel; interpret=True pinned)
# ---------------------------------------------------------------------------
from repro.index.signatures import hamming_band, make_projection, sign_signatures
from repro.kernels.hamming_filter.ops import hamming_filter_bitmap, hamming_filter_count
from repro.kernels.hamming_filter.ref import (
    hamming_filter_bitmap_ref,
    hamming_filter_count_ref,
)


def _sig_case(nq, nd, d, n_bits, seed):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(sample_uniform_sphere(rng, nq, d))
    db = jnp.asarray(sample_uniform_sphere(rng, nd, d))
    proj = make_projection(d, n_bits, seed=seed + 1)
    q_sig = jnp.asarray(sign_signatures(np.asarray(q), proj))
    db_sig = jnp.asarray(sign_signatures(np.asarray(db), proj))
    return q, db, q_sig, db_sig


@pytest.mark.parametrize("nq,nd,d,n_bits", [(64, 128, 32, 64), (100, 300, 64, 96), (33, 257, 48, 32)])
@pytest.mark.parametrize("eps", [0.3, 0.7, 1.2])
@pytest.mark.parametrize("mode", ["full", "band"])
def test_hamming_filter_count_sweep(nq, nd, d, n_bits, eps, mode):
    q, db, q_sig, db_sig = _sig_case(nq, nd, d, n_bits, seed=nq + nd)
    t_lo, t_hi = hamming_band(eps, n_bits, margin=3.0)
    if mode == "full":
        t_lo = -1
    got = np.asarray(
        hamming_filter_count(
            q, db, q_sig, db_sig, eps, t_hi, t_lo=t_lo,
            q_tile=32, db_tile=64, interpret=True,
        )
    )
    ref = np.asarray(hamming_filter_count_ref(q, db, q_sig, db_sig, eps, t_lo, t_hi))
    np.testing.assert_array_equal(got, ref)


@pytest.mark.parametrize("nq,nd", [(40, 96), (64, 257)])
@pytest.mark.parametrize("mode", ["full", "band"])
def test_hamming_filter_bitmap_sweep(nq, nd, mode):
    q, db, q_sig, db_sig = _sig_case(nq, nd, 48, 64, seed=7)
    t_lo, t_hi = hamming_band(0.6, 64, margin=3.0)
    if mode == "full":
        t_lo = -1
    gc, gb = hamming_filter_bitmap(
        q, db, q_sig, db_sig, 0.6, t_hi, t_lo=t_lo,
        q_tile=32, db_tile=64, interpret=True,
    )
    rc, rb = hamming_filter_bitmap_ref(q, db, q_sig, db_sig, 0.6, t_lo, t_hi)
    np.testing.assert_array_equal(np.asarray(gc), np.asarray(rc))
    np.testing.assert_array_equal(np.asarray(gb), np.asarray(rb))


@pytest.mark.parametrize("mode", ["full", "band"])
def test_hamming_filter_stats_match_hamming_occupancy(mode):
    """return_stats=True: the (1, 3) [accept, band, reject] occupancy
    must agree with the host Hamming occupancy of the padded tile grid,
    sum to the grid's pair count, and leave counts/bitmap unchanged."""
    from repro.index.signatures import hamming_numpy

    nq, nd, q_tile, db_tile = 40, 200, 32, 64
    q, db, q_sig, db_sig = _sig_case(nq, nd, 32, 64, seed=23)
    t_lo, t_hi = hamming_band(0.6, 64, margin=3.0)
    if mode == "full":
        t_lo = -1
    plain = np.asarray(
        hamming_filter_count(
            q, db, q_sig, db_sig, 0.6, t_hi, t_lo=t_lo,
            q_tile=q_tile, db_tile=db_tile, interpret=True,
        )
    )
    gc, stats = hamming_filter_count(
        q, db, q_sig, db_sig, 0.6, t_hi, t_lo=t_lo,
        q_tile=q_tile, db_tile=db_tile, interpret=True, return_stats=True,
    )
    gc2, gb, stats2 = hamming_filter_bitmap(
        q, db, q_sig, db_sig, 0.6, t_hi, t_lo=t_lo,
        q_tile=q_tile, db_tile=db_tile, interpret=True, return_stats=True,
    )
    np.testing.assert_array_equal(np.asarray(gc), plain)
    np.testing.assert_array_equal(np.asarray(gc2), plain)
    stats, stats2 = np.asarray(stats), np.asarray(stats2)
    np.testing.assert_array_equal(stats, stats2)
    nqt, ndt = -(-nq // q_tile), -(-nd // db_tile)
    assert stats.shape == (1, 3)
    assert stats.sum() == nqt * q_tile * ndt * db_tile

    # host occupancy on the same zero-padded tile grid
    qs = np.zeros((nqt * q_tile, q_sig.shape[1]), np.uint32)
    qs[:nq] = np.asarray(q_sig)
    ds = np.zeros((ndt * db_tile, db_sig.shape[1]), np.uint32)
    ds[:nd] = np.asarray(db_sig)
    ham = hamming_numpy(qs, ds)
    accept = ham <= t_lo
    band = (ham <= t_hi) & ~accept
    assert stats[0, 0] == accept.sum()
    assert stats[0, 1] == band.sum()


def test_hamming_filter_open_threshold_equals_range_count():
    """t_hi = n_bits (full verify) disables the filter: the fused kernel
    must reproduce the plain range_count oracle exactly."""
    q, db, q_sig, db_sig = _sig_case(48, 200, 32, 64, seed=11)
    for eps in (0.4, 0.8):
        got = np.asarray(
            hamming_filter_count(
                q, db, q_sig, db_sig, eps, 64, q_tile=32, db_tile=64, interpret=True
            )
        )
        ref = np.asarray(range_count_ref(q, db, eps))
        np.testing.assert_array_equal(got, ref)


def test_hamming_filter_closed_threshold_prunes_everything():
    """t_hi = -1 prunes every pair: the zero-candidate branch must skip
    the verify matmul in every tile and still write zero counts."""
    q, db, q_sig, db_sig = _sig_case(32, 64, 32, 64, seed=13)
    got = np.asarray(
        hamming_filter_count(
            q, db, q_sig, db_sig, 0.5, -1, q_tile=32, db_tile=64, interpret=True
        )
    )
    np.testing.assert_array_equal(got, np.zeros(32, np.int32))
    gc, gb = hamming_filter_bitmap(
        q, db, q_sig, db_sig, 0.5, -1, q_tile=32, db_tile=64, interpret=True
    )
    np.testing.assert_array_equal(np.asarray(gc), np.zeros(32, np.int32))
    np.testing.assert_array_equal(np.asarray(gb), np.zeros((32, 2), np.uint32))


def test_hamming_filter_all_sure_accept_skips_matmul():
    """t_lo = n_bits sure-accepts every pair: no tile has band
    candidates, so no verify matmul runs, yet every pair must be a hit
    (counts = nd) regardless of eps."""
    nq, nd, n_bits = 32, 100, 64  # nd not a multiple of db_tile
    q, db, q_sig, db_sig = _sig_case(nq, nd, 32, n_bits, seed=17)
    for eps in (0.3, 1.2):
        got = np.asarray(
            hamming_filter_count(
                q, db, q_sig, db_sig, eps, n_bits, t_lo=n_bits,
                q_tile=32, db_tile=64, interpret=True,
            )
        )
        np.testing.assert_array_equal(got, np.full(nq, nd, np.int32))
        gc, gb = hamming_filter_bitmap(
            q, db, q_sig, db_sig, eps, n_bits, t_lo=n_bits,
            q_tile=32, db_tile=64, interpret=True,
        )
        np.testing.assert_array_equal(np.asarray(gc), np.full(nq, nd, np.int32))
        rc, rb = hamming_filter_bitmap_ref(q, db, q_sig, db_sig, eps, n_bits, n_bits)
        np.testing.assert_array_equal(np.asarray(gb), np.asarray(rb))


def test_hamming_filter_padded_row_sure_accept_correction():
    """Zero-padded db rows can pass the *Hamming* side of the band even
    at eps < 1 (their distance to query i is popcount(q_sig_i)); the
    dual-threshold pad correction must subtract those sure-accepts."""
    nq, nd, n_bits = 32, 70, 64  # pads 70 -> 128 db rows
    q, db, q_sig, db_sig = _sig_case(nq, nd, 32, n_bits, seed=19)
    # t_lo = n_bits: every padded row would sure-accept uncorrected
    got = np.asarray(
        hamming_filter_count(
            q, db, q_sig, db_sig, 0.5, n_bits, t_lo=n_bits,
            q_tile=32, db_tile=64, interpret=True,
        )
    )
    np.testing.assert_array_equal(got, np.full(nq, nd, np.int32))


# ---------------------------------------------------------------------------
# compiled-tile contract: a tile the TPU block rule refuses raises before
# lowering (interpret mode accepts any tile that divides the operands)
# ---------------------------------------------------------------------------


def test_hamming_filter_compiled_tile_rule_raises():
    from repro.kernels.hamming_filter.kernel import hamming_filter_pallas

    q, db, q_sig, db_sig = _sig_case(64, 128, 32, 64, seed=29)
    with pytest.raises(ValueError, match="q_tile"):
        hamming_filter_pallas(
            q, db, q_sig, db_sig, 0.5, -1, 64, q_tile=32, db_tile=64, interpret=False
        )
    with pytest.raises(ValueError, match="db_tile"):
        hamming_filter_pallas(
            q, db, q_sig, db_sig, 0.5, -1, 64, q_tile=64, db_tile=64, interpret=False
        )


@pytest.mark.parametrize("row_tile,word_tile,match", [(4, 2, "row_tile"), (8, 2, "word_tile")])
def test_label_prop_compiled_tile_rule_raises(row_tile, word_tile, match):
    from repro.kernels.label_prop.kernel import col_reduce_pallas, label_prop_rect_pallas

    r, w = 16, 4
    bitmap = jnp.zeros((r, w), jnp.uint32)
    vec = jnp.zeros((r,), jnp.int32)
    with pytest.raises(ValueError, match=match):
        label_prop_rect_pallas(
            vec, jnp.zeros((w * 32,), jnp.int32), bitmap,
            row_tile=row_tile, word_tile=word_tile, interpret=False,
        )
    with pytest.raises(ValueError, match=match):
        col_reduce_pallas(
            bitmap, vec, vec, row_tile=row_tile, word_tile=word_tile, interpret=False
        )


@pytest.mark.parametrize("nd", [1, 31, 32, 33, 77, 100])
def test_host_pack_bitmap_matches_pack_bits(nd):
    """The host pack/unpack pair keeps the LSB-first word order of the
    traceable ``pack_bits`` the kernels and the sweep use."""
    from repro.core.range_query import pack_bitmap, unpack_bitmap
    from repro.index.signatures import pack_bits

    rng = np.random.default_rng(nd)
    hits = rng.random((13, nd)) < 0.3
    words = -(-nd // 32)
    padded = np.zeros((13, words * 32), bool)
    padded[:, :nd] = hits
    packed = pack_bitmap(hits)
    assert packed.dtype == np.uint32 and packed.shape == (13, words)
    np.testing.assert_array_equal(packed, np.asarray(pack_bits(jnp.asarray(padded))))
    np.testing.assert_array_equal(unpack_bitmap(packed, nd), hits)
