"""Device-resident telemetry (repro.obs v2): per-round fused-loop
counters vs a bit-exact numpy oracle (single device and 4-forced-device
mesh, ragged n), sweep occupancy slab parity vs the per-chunk kernel
stats, synthetic per-round span round-trip through the Chrome trace,
the histogram zero-clamp, and the SLO plane."""

import json

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from repro import obs
from repro.core.range_query import pack_bitmap
from repro.kernels.label_prop import packed_cluster_labels
from repro.obs import device as obs_device
from repro.obs import metrics, slo

BIG = np.iinfo(np.int32).max
EPS = 0.45


@pytest.fixture(autouse=True)
def obs_sandbox():
    """Clean, fully-enabled obs state (trace + metrics + device
    telemetry) per test; ambient switches restored afterwards."""
    was_trace, was_metrics = obs.trace_enabled(), obs.metrics_enabled()
    was_device = obs_device.device_enabled()
    obs.enable(trace=True, metrics_on=True, telemetry=True)
    obs.clear_trace()
    metrics.reset()
    yield
    obs.clear_trace()
    metrics.reset()
    if was_trace or was_metrics:
        obs.enable(trace=was_trace, metrics_on=was_metrics)
    else:
        obs.disable()
    (obs_device.enable_device if was_device else obs_device.disable_device)()


# ---------------------------------------------------------------------------
# cluster fixpoint per-round counters vs a numpy replay of the loop body
# ---------------------------------------------------------------------------


def _ragged_adjacency(n: int, seed: int, density: float = 0.012):
    rng = np.random.default_rng(seed)
    hit = rng.random((n, n)) < density
    hit = hit | hit.T
    np.fill_diagonal(hit, True)
    return hit


def _oracle_rounds(hit, rows, tau, n, cap, max_iters=64):
    """Numpy replay of ``packed_cluster_fixpoint``'s loop body — the
    independent definition the device counters are held to.  Single
    "shard", so the gather-win marginal degenerates to the frontier."""
    rows = np.asarray(rows, np.int64)
    valid = rows < n
    counts = np.where(valid, hit.sum(axis=1), 0)
    core_r = valid & (counts >= tau)
    safe = np.minimum(rows, cap - 1)
    core_c = np.zeros(cap, bool)
    core_c[safe[core_r]] = True
    lab = np.where(core_c, np.arange(cap, dtype=np.int64), BIG)
    tele = {f: [] for f in obs_device.CLUSTER_ROUND_FIELDS}
    rounds, changed = 0, True
    while changed and rounds < max_iters:
        # gather: per row, min label over set bits (BIG when empty)
        masked = np.where(hit, lab[None, :n], BIG)
        m = masked.min(axis=1, initial=BIG)
        wins = int(np.sum(core_r & (m < lab[safe])))
        new_r = np.where(core_r, np.minimum(lab[safe], m), BIG)
        front = int(np.sum(core_r & (new_r < lab[safe])))
        new = lab.copy()
        np.minimum.at(new, safe, new_r)
        jump = np.where(new < cap, new, 0)
        jumped = np.where(new < cap, np.minimum(new, new[jump]), new)
        hops = int(np.sum(jumped < new))
        chg = int(np.sum(jumped != lab))
        tele["frontier"].append(front)
        tele["changed"].append(chg)
        tele["hops"].append(hops)
        tele["shard_wins"].append(wins)
        lab, changed = jumped, chg > 0
        rounds += 1
    return {"labels": lab, "rounds": rounds, **tele}


def test_cluster_round_counters_match_host_oracle():
    n, tau = 613, 6  # ragged vs both the word and row tiles
    hit = _ragged_adjacency(n, seed=9)
    rows = np.arange(n, dtype=np.int32)
    slab = jnp.asarray(pack_bitmap(hit))
    outs = packed_cluster_labels(
        slab, jnp.asarray(rows), tau, n=n, telemetry=True, interpret=True
    )
    assert len(outs) == 6
    rounds = int(outs[4])
    tele_dev = [np.asarray(v) for v in outs[5]]
    cap = slab.shape[1] * 32
    oracle = _oracle_rounds(hit, rows, tau, n, cap)
    assert rounds == oracle["rounds"] >= 2
    for vec, field in zip(tele_dev, obs_device.CLUSTER_ROUND_FIELDS):
        assert vec.dtype == np.int32
        np.testing.assert_array_equal(
            vec[:rounds], np.asarray(oracle[field]), err_msg=field
        )
        # slots past the fixpoint stay zero (the harvest trims on them)
        assert not vec[rounds:].any(), field
    # single shard: every gather win is a frontier row and vice versa
    assert oracle["shard_wins"] == oracle["frontier"]
    # telemetry is an observer: the label outputs are bit-identical to
    # the telemetry-off program
    base = packed_cluster_labels(
        slab, jnp.asarray(rows), tau, n=n, telemetry=False, interpret=True
    )
    assert len(base) == 5
    np.testing.assert_array_equal(np.asarray(outs[0]), np.asarray(base[0]))


def test_harvest_trims_and_accumulates_counters():
    n, tau = 257, 5
    hit = _ragged_adjacency(n, seed=3, density=0.03)
    rows = np.arange(n, dtype=np.int32)
    slab = jnp.asarray(pack_bitmap(hit))
    outs = packed_cluster_labels(
        slab, jnp.asarray(rows), tau, n=n, telemetry=True, interpret=True
    )
    rounds = int(outs[4])
    host = jax.device_get(outs[5])
    per_round = obs_device.harvest_cluster_telemetry(host, rounds)
    assert set(per_round) == set(obs_device.CLUSTER_ROUND_FIELDS)
    assert all(len(v) == rounds for v in per_round.values())
    snap = metrics.snapshot()
    for f, vals in per_round.items():
        assert snap[f"laf.telemetry.{f}"] == sum(vals)


@pytest.mark.slow
def test_mesh_shard_counters_match_single_device(forced_device_run):
    """4-device mesh, ragged n: the psum'd per-round vectors must be
    bit-identical to the single-device run for the replicated
    quantities (frontier/changed/hops track the *post*-pmin state), and
    the shard-win marginal must dominate the frontier while collapsing
    to it off-mesh."""
    out = forced_device_run("""
    import numpy as np, jax, jax.numpy as jnp
    from jax.sharding import Mesh
    from repro.core.range_query import pack_bitmap
    from repro.distributed.index_plane import sharded_cluster_labels
    from repro.kernels.label_prop import packed_cluster_labels

    rng = np.random.default_rng(9)
    n, tau = 613, 6
    hit = rng.random((n, n)) < 0.012
    hit = hit | hit.T
    np.fill_diagonal(hit, True)
    slab_np = pack_bitmap(hit)
    w = slab_np.shape[1]
    pad_w = (-w) % 4  # whole words per shard
    if pad_w:
        slab_np = np.pad(slab_np, ((0, 0), (0, pad_w)))
    # pad rows so the shard-local row tile divides the slab (sentinel
    # rows >= n are no-ops in the fixpoint)
    pad_r = (-n) % 128
    slab_np = np.pad(slab_np, ((0, pad_r), (0, 0)))
    rows = np.full(n + pad_r, n, np.int32)
    rows[:n] = np.arange(n)

    slab, rows_j = jnp.asarray(slab_np), jnp.asarray(rows)
    single = packed_cluster_labels(
        slab, rows_j, tau, n=n, telemetry=True, interpret=True)
    mesh = Mesh(np.asarray(jax.devices()), ("data",))
    shard = sharded_cluster_labels(
        slab, rows_j, tau, mesh=mesh, axes=("data",), n=n,
        telemetry=True, interpret=True)
    s_rounds, m_rounds = int(single[4]), int(shard[4])
    R = m_rounds
    s_t = [np.asarray(v) for v in single[5]]
    m_t = [np.asarray(v) for v in shard[5]]
    print("RESULT:" + __import__("json").dumps({
        "rounds_equal": s_rounds == m_rounds,
        "rounds": m_rounds,
        "labels_equal": bool(np.array_equal(
            np.asarray(single[0]), np.asarray(shard[0]))),
        "frontier_equal": bool(np.array_equal(s_t[0][:R], m_t[0][:R])),
        "changed_equal": bool(np.array_equal(s_t[1][:R], m_t[1][:R])),
        "hops_equal": bool(np.array_equal(s_t[2][:R], m_t[2][:R])),
        "wins_ge_frontier": bool((m_t[3][:R] >= m_t[0][:R]).all()),
        "single_wins_eq_frontier": bool(
            np.array_equal(s_t[3][:R], s_t[0][:R])),
    }))
    """)
    assert out["rounds_equal"] and out["rounds"] >= 2
    assert out["labels_equal"]
    assert out["frontier_equal"] and out["changed_equal"] and out["hops_equal"]
    assert out["wins_ge_frontier"]
    assert out["single_wins_eq_frontier"]


# ---------------------------------------------------------------------------
# sweep occupancy slab vs the per-chunk kernel stats
# ---------------------------------------------------------------------------


def test_sweep_telemetry_slab_matches_per_chunk_stats():
    """The one-launch engine's donated stats slab must hold, per chunk
    row, exactly the tile-summed occupancy the standalone per-chunk
    kernel reports for the same operands — including the zero-padded
    tail chunk — and telemetry must not move a single count."""
    from repro.data.synthetic import make_angular_clusters
    from repro.index import RandomProjectionBackend
    from repro.kernels.hamming_filter.ops import hamming_filter_count

    n, d = 150, 16  # ragged vs chunk=64: 3 live chunks, 1 pad chunk
    data, _ = make_angular_clusters(n, d, 4, kappa=60, noise_frac=0.2, seed=2)
    bk = RandomProjectionBackend(
        n_bits=64, seed=2, device=True, interpret=True, sweep=True,
        chunk=64, chunks_per_launch=2, q_tile=32, db_tile=128,
    ).fit(data)
    rows = np.arange(n)
    counts_on = np.asarray(bk.query_counts(rows, EPS))
    slab = obs_device.last_sweep_stats()
    assert slab is not None and slab.shape[1] == 3
    snap = metrics.snapshot()
    totals = slab.sum(axis=0)
    for i, f in enumerate(obs_device.SWEEP_STAT_FIELDS):
        assert snap[f"sweep.tele.{f}"] == totals[i]

    obs_device.disable_device()
    counts_off = np.asarray(bk.query_counts(rows, EPS))
    np.testing.assert_array_equal(counts_on, counts_off)

    # reference: run each (zero-padded) chunk through the per-chunk
    # kernel with stats and tile-sum — identical operands => identical
    # padded tile grids => identical triples
    t_lo, t_hi = bk.band(EPS)
    q, q_sig = bk._sweep_q(rows)
    db, dbs = bk._sweep_db()
    chunk, n_rows = 64, slab.shape[0] * 64
    qp = np.zeros((n_rows, q.shape[1]), np.float32)
    qsp = np.zeros((n_rows, q_sig.shape[1]), np.uint32)
    qp[:n], qsp[:n] = np.asarray(q), np.asarray(q_sig)
    for k in range(slab.shape[0]):
        sl = slice(k * chunk, (k + 1) * chunk)
        _, stats = hamming_filter_count(
            jnp.asarray(qp[sl]), db, jnp.asarray(qsp[sl]), dbs,
            EPS, t_hi, t_lo=t_lo, q_tile=32, db_tile=128,
            interpret=True, return_stats=True,
        )
        ref = np.asarray(obs_device.sweep_stats_tile_sum(stats))
        np.testing.assert_array_equal(slab[k], ref, err_msg=f"chunk {k}")


# ---------------------------------------------------------------------------
# synthetic per-round spans: emission + Chrome-trace round-trip
# ---------------------------------------------------------------------------


def test_synthetic_round_spans_roundtrip_chrome_trace(tmp_path):
    import time

    with obs.span("laf.label_prop", rows=8) as sp:
        time.sleep(0.01)
    parent = sp._rec
    per_round = {
        "frontier": [5, 3, 1], "changed": [6, 3, 0],
        "hops": [2, 1, 0], "shard_wins": [5, 3, 1],
    }
    recs = obs_device.emit_round_spans(parent, per_round)
    assert len(recs) == 3
    # equal subdivision of the parent interval, fully attributing it
    assert recs[0].t0 == parent.t0
    assert all(r.dur == pytest.approx(parent.dur / 3) for r in recs)
    assert recs[-1].t0 + recs[-1].dur == pytest.approx(parent.t0 + parent.dur)
    assert obs.coverage(parent) == pytest.approx(1.0)

    p = tmp_path / "trace.json"
    obs.export_chrome_trace(str(p))
    evs = json.loads(p.read_text())["traceEvents"]
    parent_ev = next(e for e in evs if e["name"] == "laf.label_prop")
    rounds = [e for e in evs if e["name"] == "laf.cluster.round"]
    assert len(rounds) == 3
    for i, e in enumerate(sorted(rounds, key=lambda e: e["ts"])):
        assert e["args"]["parent_id"] == parent_ev["args"]["span_id"]
        assert e["args"]["synthetic"] is True
        assert e["args"]["round"] == i
        assert e["args"]["frontier"] == per_round["frontier"][i]
        assert e["ts"] >= parent_ev["ts"]


def test_emit_round_spans_noops_safely():
    # no parent record (span taken while tracing was off), no rounds,
    # zero-duration parent: all decline without touching the buffer
    before = len(obs.spans())
    assert obs_device.emit_round_spans(None, {"frontier": [1]}) == []
    with obs.span("p") as sp:
        pass
    assert obs_device.emit_round_spans(sp._rec, {"frontier": []}) == []
    assert len(obs.spans()) == before + 1


# ---------------------------------------------------------------------------
# histogram zero/sub-resolution clamp
# ---------------------------------------------------------------------------


def test_histogram_clamps_zero_to_first_bound():
    h = metrics.histogram("tele.h", bounds=(1e-4, 1e-3, 1e-2))
    for v in (0.0, -0.0, 1e-9, 1e-4):  # all at or below the first bound
        h.observe(v)
    assert h.count == 4
    assert h._counts[0] == 4
    assert h._min == 1e-4  # raw zeros must not drag the interpolation
    assert h.quantile(0.5) == pytest.approx(1e-4)
    s = h.summary()
    assert s["min"] == 1e-4 and s["p50"] == pytest.approx(1e-4)
    h.observe(5e-3)  # above the clamp: normal bucketing unaffected
    assert h._counts[0] == 4 and h.count == 5
    assert h._max == 5e-3


# ---------------------------------------------------------------------------
# SLO plane
# ---------------------------------------------------------------------------


def test_slo_evaluate_registry_and_derived_values():
    rules = [
        slo.SLO("lat-p99", "t.lat:p99", "<=", 1.0),
        slo.SLO("runs-floor", "t.runs", ">=", 1.0),
        slo.SLO("derived-ari", "run.ari", ">=", 0.99),
    ]
    # no data anywhere: every rule is "no data", nothing is violated
    res = slo.evaluate(rules)
    assert all(r.ok is None and not r.violated for r in res)

    metrics.counter("t.runs").inc(3)
    h = metrics.histogram("t.lat")
    for _ in range(100):
        h.observe(0.01)
    res = slo.evaluate(rules, values={"run.ari": 0.995})
    by = {r.slo.name: r for r in res}
    assert by["lat-p99"].ok and by["runs-floor"].ok and by["derived-ari"].ok
    # a derived value takes precedence and can violate
    res = slo.evaluate(rules, values={"run.ari": 0.5})
    assert {r.slo.name: r.violated for r in res}["derived-ari"]


def test_slo_check_and_alert_counts_and_warns(caplog):
    import logging

    rules = [slo.SLO("always-bad", "x.val", "<=", 0.0)]
    metrics.counter("x.val").inc(5)
    with caplog.at_level(logging.WARNING, logger="repro.obs.slo"):
        res = slo.check_and_alert(rules, interval_s=0.0)
    assert res[0].violated
    snap = metrics.snapshot()
    assert snap["slo.evaluations"] == 1 and snap["slo.violations"] == 1
    text = "\n".join(r.getMessage() for r in caplog.records)
    assert "slo.violation" in text and "always-bad" in text


def test_slo_invalid_op_rejected():
    with pytest.raises(ValueError):
        slo.SLO("bad", "m", "!=", 1.0)


def test_default_slo_sets_cover_the_stack():
    for kind, rules in (
        ("serve", slo.SERVE_SLOS), ("ingest", slo.INGEST_SLOS),
        ("cluster", slo.CLUSTER_SLOS),
    ):
        assert rules, kind
        assert all(isinstance(r, slo.SLO) for r in rules)
