"""The sharded index plane (``repro.distributed.index_plane``) and the
LAF lowering built on it.

Three layers of parity, all against the one shared ``band_hits``
contract:

* plane functions on a 1-device mesh == the plain kernel wrappers
  (in-process; the degenerate case ``index_device="auto"`` now relies
  on);
* plane functions on a forced 4-host-device mesh == host oracle ==
  single-device fused path, on a non-shard-multiple ``n`` (subprocess —
  the device count is locked at first jax init);
* ``build_laf_cluster`` with ``index_device="auto"`` on the 4-device
  mesh routes through the shard_mapped tile (meta says so) and its
  frontier round reproduces the dataflow lowering bit-for-bit, while
  end-to-end clustering through the plane-backed backend matches the
  exact backend at ARI == 1.0.
"""

import numpy as np
import pytest

from repro.data.synthetic import make_angular_clusters

EPS = 0.55


@pytest.fixture(scope="module")
def plane_data():
    # 613 is not a multiple of 4 shards (nor of 32): plane-level padding
    # and the padded-row corrections are exercised on every call
    data, _ = make_angular_clusters(613, 32, 8, kappa=120, noise_frac=0.3, seed=2)
    return data


# ---------------------------------------------------------------------------
# sharding helpers
# ---------------------------------------------------------------------------


def test_data_axes_is_public_dp_spelling():
    import jax
    from jax.sharding import Mesh

    from repro.distributed.sharding import _dp_axes, data_axes

    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    assert data_axes(mesh) == ("data",)
    assert _dp_axes is data_axes  # the private name is the same object


def test_shard_plan_alignment():
    import jax
    from jax.sharding import Mesh

    from repro.distributed.index_plane import shard_plan

    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    plan = shard_plan(mesh, 613)
    assert plan.axes == ("data",)
    assert plan.n_shards == 1
    assert plan.n_padded % 32 == 0 and plan.n_padded >= 613
    plan_all = shard_plan(mesh, 613, axes=("data", "model"))
    assert plan_all.axes == ("data", "model")


def test_shard_signatures_places_and_pads(plane_data):
    import jax
    from jax.sharding import Mesh

    from repro.index import RandomProjectionBackend, shard_signatures

    bk = RandomProjectionBackend(n_bits=64, seed=3).fit(plane_data)
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    placed = shard_signatures(mesh, bk.signatures, n_padded=640)
    assert placed.shape == (640, 2)
    got = np.asarray(placed)
    np.testing.assert_array_equal(got[:613], bk.signatures)
    assert not got[613:].any()  # zero-word padding


# ---------------------------------------------------------------------------
# 1-device degenerate case: the plane IS the plain wrapper
# ---------------------------------------------------------------------------


def test_plane_single_device_matches_plain_kernel(plane_data):
    import jax
    from jax.sharding import Mesh

    from repro.distributed.index_plane import (
        sharded_band_marginals,
        sharded_hamming_bitmap,
        sharded_hamming_count,
    )
    from repro.index import RandomProjectionBackend
    from repro.kernels.hamming_filter.ops import (
        hamming_filter_bitmap,
        hamming_filter_count,
    )

    bk = RandomProjectionBackend(n_bits=64, seed=3).fit(plane_data)
    t_lo, t_hi = bk.band(EPS)
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    q, q_sig = plane_data[:48], bk.signatures[:48]
    kw = dict(t_lo=t_lo, q_tile=32, db_tile=64, interpret=True)

    ref_c = hamming_filter_count(q, plane_data, q_sig, bk.signatures, EPS, t_hi, **kw)
    ref_c2, ref_bm = hamming_filter_bitmap(
        q, plane_data, q_sig, bk.signatures, EPS, t_hi, **kw
    )
    got_c = sharded_hamming_count(
        q, plane_data, q_sig, bk.signatures, EPS, t_hi, mesh=mesh, **kw
    )
    got_c2, got_bm = sharded_hamming_bitmap(
        q, plane_data, q_sig, bk.signatures, EPS, t_hi, mesh=mesh, **kw
    )
    np.testing.assert_array_equal(np.asarray(got_c), np.asarray(ref_c))
    np.testing.assert_array_equal(np.asarray(got_c2), np.asarray(ref_c2))
    np.testing.assert_array_equal(np.asarray(got_bm), np.asarray(ref_bm))

    counts_m, partial = sharded_band_marginals(
        q, plane_data, q_sig, bk.signatures, EPS, t_hi, mesh=mesh, **kw
    )
    from repro.core.range_query import unpack_bitmap

    hits = unpack_bitmap(np.asarray(ref_bm), len(plane_data))
    np.testing.assert_array_equal(np.asarray(counts_m), hits.sum(axis=1))
    np.testing.assert_array_equal(np.asarray(partial), hits.sum(axis=0))


def test_backend_mesh_single_device_matches_host(plane_data):
    import jax
    from jax.sharding import Mesh

    from repro.index import RandomProjectionBackend

    cfg = dict(n_bits=64, margin=3.0, seed=3, chunk=64, q_tile=32, db_tile=64)
    host = RandomProjectionBackend(device=False, **cfg).fit(plane_data)
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    plane = RandomProjectionBackend(
        device=True, interpret=True, mesh=mesh, **cfg
    ).fit(plane_data)
    rows = np.arange(80)
    for eps in (EPS, 1.2):  # eps > 1: plane-pad rows pass the dot test
        hh = host.query_hits(rows, eps)
        np.testing.assert_array_equal(plane.query_hits(rows, eps), hh)
        np.testing.assert_array_equal(plane.query_counts(rows, eps), hh.sum(axis=1))
        cols = np.arange(5, 600, 7)
        np.testing.assert_array_equal(
            plane.query_hits_subset(rows, cols, eps), hh[:, cols]
        )


# ---------------------------------------------------------------------------
# forced 4-host-device mesh (subprocess): real shards
# ---------------------------------------------------------------------------


def test_plane_4dev_parity_nonmultiple_n(forced_device_run):
    """Sharded-plane hits/counts == host oracle == single-device fused
    path on n = 613 (not a multiple of shards, kernel tiles, or 32)."""
    out = forced_device_run(
        """
        import numpy as np, jax
        from repro.data.synthetic import make_angular_clusters
        from repro.index import RandomProjectionBackend

        data, _ = make_angular_clusters(613, 32, 8, kappa=120, noise_frac=0.3, seed=2)
        mesh = jax.make_mesh((4,), ("data",))
        cfg = dict(n_bits=64, margin=3.0, seed=3, chunk=64, q_tile=32, db_tile=64)
        host = RandomProjectionBackend(device=False, **cfg).fit(data)
        single = RandomProjectionBackend(device=True, interpret=True, **cfg).fit(data)
        plane = RandomProjectionBackend(
            device=True, interpret=True, mesh=mesh, **cfg
        ).fit(data)
        assert plane._plan.n_shards == 4

        rows = np.arange(96)
        ok = {}
        for eps in (0.55, 1.2):
            hh = host.query_hits(rows, eps)
            np.testing.assert_array_equal(single.query_hits(rows, eps), hh)
            np.testing.assert_array_equal(plane.query_hits(rows, eps), hh)
            np.testing.assert_array_equal(
                plane.query_counts(rows, eps), hh.sum(axis=1)
            )
            np.testing.assert_array_equal(
                single.query_counts(rows, eps), hh.sum(axis=1)
            )
            ok[str(eps)] = True
        print("RESULT:" + json.dumps(ok))
        """
    )
    assert out["0.55"] and out["1.2"]


def test_laf_cluster_auto_routes_sharded_tile_4dev(forced_device_run):
    """Acceptance: on a forced 4-host-device mesh, ``index_device="auto"``
    routes the frontier round through the shard_mapped hamming_filter
    tile (no n_dev == 1 special case), reproduces the dataflow lowering
    bit-for-bit, and clustering through the plane-backed backend gives
    labels with ARI == 1.0 vs the exact backend."""
    out = forced_device_run(
        """
        import dataclasses
        import numpy as np, jax, jax.numpy as jnp

        from repro.configs.registry import get_arch
        from repro.core.laf_dbscan import laf_dbscan
        from repro.core.metrics import adjusted_rand_index
        from repro.data.synthetic import make_angular_clusters
        from repro.index import ExactBackend, RandomProjectionBackend
        from repro.index.signatures import make_projection, sign_signatures
        from repro.launch import laf_cluster as L

        arch = get_arch("laf_dbscan")
        base = arch.make_reduced_config()
        shape = dataclasses.replace(
            arch.shapes["nyt_150k"], meta={"n_points": 512, "dim": 32}
        )
        mesh = jax.make_mesh((4,), ("data",))

        def cell_for(index_device):
            red = dataclasses.replace(
                base, backend="random_projection", index_device=index_device
            )
            a = dataclasses.replace(arch, make_config=lambda: red)
            return L.build_laf_cluster(a, shape, mesh)

        auto_cell = cell_for("auto")
        flow_cell = cell_for(False)
        meta = {
            "fused": bool(auto_cell.meta["fused_kernel"]),
            "sharded": bool(auto_cell.meta["sharded"]),
            "n_shards": int(auto_cell.meta["n_shards"]),
            "flow_fused": bool(flow_cell.meta["fused_kernel"]),
        }

        rng = np.random.default_rng(1)
        from repro.data.synthetic import sample_uniform_sphere
        data = sample_uniform_sphere(rng, 512, 32)
        queries = data[: base.frontier]
        db_sig = sign_signatures(data, make_projection(32, base.index_bits, seed=0))
        params = jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype), auto_cell.args[0]
        )
        args = (params, data, queries, jnp.asarray(db_sig))
        with mesh:
            fused = [np.asarray(o) for o in auto_cell.step_fn(*args)]
            flow = [np.asarray(o) for o in flow_cell.step_fn(*args)]
        meta["partial_sum"] = int(fused[1].sum())
        np.testing.assert_array_equal(fused[0], flow[0])
        np.testing.assert_array_equal(fused[1], flow[1])

        # end-to-end labels through the same plane: open-filter full
        # verify makes the indexed hit sets *equal* to exact, so the
        # partitions are identical, deterministically
        cdata, _ = make_angular_clusters(600, 32, 8, kappa=120, noise_frac=0.3, seed=5)
        exact = ExactBackend().fit(cdata)
        plane = RandomProjectionBackend(
            n_bits=64, margin=1e9, verify="full", seed=4,
            device=True, interpret=True, mesh=mesh, chunk=64,
            q_tile=32, db_tile=64,
        ).fit(cdata)
        pred = exact.query_counts(np.arange(len(cdata)), 0.55)
        res_ex = laf_dbscan(cdata, 0.55, 5, 1.0, pred, seed=0, backend=exact)
        res_pl = laf_dbscan(cdata, 0.55, 5, 1.0, pred, seed=0, backend=plane)
        meta["ari"] = float(adjusted_rand_index(res_ex.labels, res_pl.labels))
        print("RESULT:" + json.dumps(meta))
        """,
        timeout=600,
    )
    assert out["fused"] is True and out["sharded"] is True
    assert out["n_shards"] == 4
    assert out["flow_fused"] is False
    assert out["partial_sum"] > 0
    assert out["ari"] == 1.0


# ---------------------------------------------------------------------------
# kernel occupancy stats + margin auto-tune
# ---------------------------------------------------------------------------


def test_suggest_margin_host_device_agree(plane_data):
    from repro.index import RandomProjectionBackend, suggest_margin

    cfg = dict(n_bits=64, seed=3, q_tile=32, db_tile=64)
    host = RandomProjectionBackend(device=False, **cfg).fit(plane_data)
    dev = RandomProjectionBackend(device=True, interpret=True, **cfg).fit(plane_data)
    m_host, table = suggest_margin(host, EPS, report=True)
    m_dev = suggest_margin(dev, EPS)
    assert m_host == m_dev
    assert any(r["margin"] == m_host for r in table)
    # band width (and so its occupancy) grows with margin
    fracs = [r["band_frac"] for r in sorted(table, key=lambda r: r["margin"])]
    assert fracs == sorted(fracs)


def test_suggest_margin_budget_monotone(plane_data):
    from repro.index import RandomProjectionBackend, suggest_margin

    bk = RandomProjectionBackend(n_bits=64, seed=3, device=False).fit(plane_data)
    loose = suggest_margin(bk, EPS, max_band_frac=0.9)
    tight = suggest_margin(bk, EPS, max_band_frac=0.05)
    assert loose >= tight  # a bigger verify budget affords a wider band


def test_sharded_cluster_tiles_raise_instead_of_shrinking():
    """A shard-local slab whose words cannot fill a 128-word tile must
    raise for a compiled launch, not fall back to a smaller gcd tile."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from repro.distributed.index_plane import sharded_cluster_labels

    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    with pytest.raises(ValueError, match="word_tile"):
        sharded_cluster_labels(
            jnp.zeros((256, 136), jnp.uint32), jnp.arange(256, dtype=jnp.int32), 2,
            mesh=mesh, axes=("data",), n=4000, interpret=False,
        )
