"""What the sharded index plane moves, on a forced 4-host-device mesh:
LAF-DBSCAN through ``RandomProjectionBackend(mesh=...)`` gives the
one-device labels, uploads the rescue's column side once per call
however many blocks the rescue sweeps, puts the database's shards and
the sweep's queries through synced ``laf.upload`` spans, and counts its
collectives in ``plane.collective.bytes`` as the launch shapes and the
cluster pass's rounds reckon them, and keeps the slab's words sharded."""

import pytest

_SNIPPET = """
import numpy as np, jax
from repro import obs
from repro.obs import metrics
from repro.core.laf_dbscan import laf_dbscan
from repro.data.synthetic import make_angular_clusters
from repro.index import ExactBackend, RandomProjectionBackend
from repro.index.sweep import plan_sweep

data, _ = make_angular_clusters(1024, 32, 8, kappa=80, noise_frac=0.35, seed=5)
EPS, TAU = 0.25, 5
# heavy under-estimation: many skipped core points for the rescue
pred = ExactBackend().fit(data).query_counts(np.arange(len(data)), EPS) * 0.5
cfg = dict(n_bits=64, seed=3, device=True, interpret=True, chunk=64,
           q_tile=32, db_tile=64)
mesh = jax.make_mesh((4,), ("data",))
obs.enable(trace=True)


def counters():
    return {k: v for k, v in metrics.snapshot().items() if isinstance(v, (int, float))}


def call(block, on_mesh):
    obs.clear_trace()
    before = counters()
    bk = RandomProjectionBackend(mesh=mesh if on_mesh else None, **cfg)
    res = laf_dbscan(data, EPS, TAU, 1.0, pred, block_size=block, backend=bk)
    delta = {k: v - before.get(k, 0) for k, v in counters().items()}
    ups = [[r.attrs["what"], r.attrs["rows"], r.attrs["bytes"]]
           for r in obs.spans("laf.upload")]
    out = {"labels": res.labels.tolist(), "n_rescued": res.extras["n_rescued"],
           "n_exec": res.extras["n_predicted_core"], "uploads": ups,
           "rescue_blocks": len(obs.spans("laf.rescue.sweep")),
           "delta": {k: delta.get(k, 0) for k in (
               "index.upload.bytes", "plane.collective.bytes", "plane.psum.bytes",
               "plane.gather.bytes", "laf.cluster.rounds")}}
    if on_mesh:
        plan = plan_sweep(out["n_exec"], cfg["chunk"], cfg["q_tile"], bk.chunks_per_launch)
        out["slab_rows"] = plan.nq_padded
        out["n_padded"] = bk._plan.n_padded
    return out


plane_bk = RandomProjectionBackend(mesh=mesh, **cfg).fit(data)
slab, _ = plane_bk.query_bitmap_device(np.arange(300), EPS)
one_slab, _ = RandomProjectionBackend(**cfg).fit(data).query_bitmap_device(np.arange(300), EPS)
print("RESULT:" + json.dumps({
    "one_device": call(64, False),
    "plane64": call(64, True),
    "plane128": call(128, True),
    "slab_spec": [str(a) for a in slab.sharding.spec],
    "slab_equal": bool(np.array_equal(np.asarray(slab), np.asarray(one_slab)[:, : slab.shape[1]])),
}))
"""


@pytest.fixture(scope="module")
def plane_calls(forced_device_run):
    return forced_device_run(_SNIPPET, timeout=600)


def test_plane_rescue_gives_the_one_device_labels(plane_calls):
    one, plane = plane_calls["one_device"], plane_calls["plane64"]
    assert plane["n_rescued"] > 0 and plane["rescue_blocks"] >= 3
    assert plane["n_rescued"] == one["n_rescued"] and plane["n_exec"] == one["n_exec"]
    assert plane["labels"] == one["labels"]
    assert plane_calls["plane128"]["labels"] == one["labels"]


def test_plane_rescue_uploads_its_column_side_once_per_call(plane_calls):
    d, words = 32, 64 // 32
    small, large = plane_calls["plane64"], plane_calls["plane128"]
    assert small["rescue_blocks"] > large["rescue_blocks"] >= 2
    for call in (small, large):
        cols = [u for u in call["uploads"] if u[0].startswith("rescue_col")]
        n = call["n_rescued"]
        assert sorted(cols) == [["rescue_col_sigs", n, 4 * n * words],
                                ["rescue_cols", n, 4 * n * d]]
    # every other copy is per call or per executed row, never per block
    assert small["delta"]["index.upload.bytes"] == large["delta"]["index.upload.bytes"]
    assert small["delta"]["index.upload.bytes"] == sum(u[2] for u in small["uploads"])


def test_plane_shards_and_queries_are_uploaded_through_spans(plane_calls):
    d, words = 32, 64 // 32
    call = plane_calls["plane64"]
    by_what = {}
    for what, rows, nbytes in call["uploads"]:
        by_what.setdefault(what, []).append((rows, nbytes))
    n_pad = call["n_padded"]
    assert by_what["shard_data"] == [(n_pad, 4 * n_pad * d)]
    assert by_what["shard_sigs"] == [(n_pad, 4 * n_pad * words)]
    # the slab sweep's queries once, then each rescue block's rows
    q_rows = [rows for rows, _ in by_what["sweep_q"]]
    assert q_rows[0] == call["n_exec"] and sum(q_rows[1:]) == call["n_exec"]
    assert len(q_rows) == 1 + call["rescue_blocks"]
    assert [r for r, _ in by_what["sweep_q_sigs"]] == q_rows
    # the one-device path keeps its own copies and none of these
    one = {u[0] for u in plane_calls["one_device"]["uploads"]}
    assert not one & {"shard_data", "shard_sigs", "sweep_q", "rescue_cols"}


def test_plane_collective_bytes_equal_the_launch_reckoning(plane_calls):
    for call in (plane_calls["plane64"], plane_calls["plane128"]):
        delta, rows = call["delta"], call["slab_rows"]
        rounds = delta["laf.cluster.rounds"]
        assert rounds >= 1
        sweep_psum = 4 * rows  # one (chunk,) s32 psum per chunk of the slab sweep
        cluster = 4 * rows * (1 + rounds)  # counts psum, then one pmin a round
        assert delta["plane.psum.bytes"] == sweep_psum
        assert delta["plane.collective.bytes"] == sweep_psum + cluster
        # the slab's word blocks stay sharded: counted as a host gather only
        assert delta["plane.gather.bytes"] > 0
    assert plane_calls["one_device"]["delta"]["plane.collective.bytes"] == 0


def test_plane_slab_stays_column_sharded(plane_calls):
    """The joined, tail-masked slab keeps its words sharded over the mesh
    (the cluster program's layout) and equals the one-device slab."""
    assert plane_calls["slab_spec"] == ["None", "data"]
    assert plane_calls["slab_equal"]
