"""Durable streaming plane: WAL framing + torn-tail semantics,
checkpoint crash-safety, snapshot/restore parity, kill-restore
(boundary, mid-batch, SIGKILL subprocess), corrupt-snapshot fallback,
failover clone/promote, and seeded fault injection with graceful
degradation to the host oracles."""

import os
import signal
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro import obs
from repro.core.laf_dbscan import laf_dbscan
from repro.core.metrics import adjusted_rand_index
from repro.data.synthetic import make_angular_clusters
from repro.index import RandomProjectionBackend
from repro.obs import metrics
from repro.stream import DurableStream, StreamingLAF, clone_replica
from repro.stream.durability import (
    KIND_EVICT,
    KIND_INGEST,
    WalWriter,
    export_replica,
    import_replica,
    read_wal,
)
from repro.testing import faults
from repro.train.checkpoint import (
    CheckpointCorruptError,
    gc_checkpoints,
    list_steps,
    restore_checkpoint,
    save_checkpoint,
)

EPS, TAU = 0.35, 5


@pytest.fixture(scope="module")
def stream_data():
    data, _ = make_angular_clusters(700, 16, 8, kappa=120, noise_frac=0.3, seed=7)
    return data[np.random.default_rng(1).permutation(len(data))]


@pytest.fixture()
def obs_sandbox():
    """Clean, enabled metrics per test; ambient switches restored."""
    was_trace, was_metrics = obs.trace_enabled(), obs.metrics_enabled()
    obs.enable(trace=False, metrics_on=True)
    metrics.reset()
    yield
    metrics.reset()
    if was_trace or was_metrics:
        obs.enable(trace=was_trace, metrics_on=was_metrics)
    else:
        obs.disable()


def _factory():
    return StreamingLAF(EPS, TAU, block_size=256, backend="exact")


def _batches(data, k):
    step = -(-len(data) // k)
    return [data[i : i + step] for i in range(0, len(data), step)]


def _assert_replica_equal(a, b):
    """Bit-identical serving state: labels, owners, counts, core."""
    np.testing.assert_array_equal(a.labels(), b.labels())
    n = a.state.n
    assert n == b.state.n
    np.testing.assert_array_equal(a.state.counts[:n], b.state.counts[:n])
    np.testing.assert_array_equal(a.state.core[:n], b.state.core[:n])
    np.testing.assert_array_equal(a.state.owner[:n], b.state.owner[:n])
    np.testing.assert_array_equal(a.state.alive[:n], b.state.alive[:n])


# ---------------------------------------------------------------------------
# WAL framing
# ---------------------------------------------------------------------------


def test_wal_round_trip(tmp_path):
    p = tmp_path / "wal_000000000000.log"
    w = WalWriter(p)
    rows = np.arange(12, dtype=np.float32).reshape(3, 4)
    idx = np.array([1, 5], dtype=np.int64)
    w.append(1, KIND_INGEST, {"rows": rows})
    w.append(2, KIND_EVICT, {"idx": idx})
    w.close()
    recs = list(read_wal(p))
    assert [(s, k) for s, k, _ in recs] == [(1, KIND_INGEST), (2, KIND_EVICT)]
    np.testing.assert_array_equal(recs[0][2]["rows"], rows)
    np.testing.assert_array_equal(recs[1][2]["idx"], idx)


def test_wal_torn_tail_dropped_deterministically(tmp_path):
    p = tmp_path / "wal_000000000000.log"
    w = WalWriter(p)
    for s in range(1, 4):
        w.append(s, KIND_INGEST, {"rows": np.full((2, 3), s, dtype=np.float32)})
    w.close()
    full = p.read_bytes()
    # cut into the last record's payload: the torn tail must be dropped
    # and the surviving prefix returned, at every cut point
    last_len = len(full) - len(
        full[: full.rfind(b"PK")]  # crude: anywhere inside record 3
    )
    for cut in (1, last_len // 2, last_len - 1):
        p.write_bytes(full[: len(full) - cut])
        assert [s for s, _, _ in read_wal(p)] == [1, 2]
    # a clean file still yields everything
    p.write_bytes(full)
    assert [s for s, _, _ in read_wal(p)] == [1, 2, 3]


def test_wal_corrupt_record_stops_at_prior(tmp_path):
    p = tmp_path / "wal_000000000000.log"
    w = WalWriter(p)
    lens = [w.append(s, KIND_INGEST, {"rows": np.zeros((2, 2), np.float32)})
            for s in (1, 2)]
    w.close()
    # flip a byte inside record 2's payload: crc fails, replay stops at 1
    raw = bytearray(p.read_bytes())
    raw[8 + lens[0] + 20] ^= 0xFF
    p.write_bytes(bytes(raw))
    assert [s for s, _, _ in read_wal(p)] == [1]


def test_wal_missing_or_foreign_file_is_empty(tmp_path):
    assert list(read_wal(tmp_path / "nope.log")) == []
    p = tmp_path / "junk.log"
    p.write_bytes(b"not a wal at all")
    assert list(read_wal(p)) == []


# ---------------------------------------------------------------------------
# checkpoint crash-safety
# ---------------------------------------------------------------------------


def test_checkpoint_partial_dirs_invisible_and_collected(tmp_path):
    tree = {"a": np.arange(6, dtype=np.float32)}
    save_checkpoint(tmp_path, 1, tree, fsync=False)
    # a crash mid-write leaves a tmp- dir; a crashed legacy writer an
    # empty step dir with no manifest — neither is a restore candidate
    (tmp_path / "tmp-step_000000000002").mkdir()
    (tmp_path / "tmp-step_000000000002" / "shard_000000.npz").write_bytes(b"x")
    (tmp_path / "step_000000000003").mkdir()
    assert list_steps(tmp_path) == [1]
    gc_checkpoints(tmp_path, keep=3)
    assert not (tmp_path / "tmp-step_000000000002").exists()
    assert not (tmp_path / "step_000000000003").exists()
    assert list_steps(tmp_path) == [1]


def test_checkpoint_checksum_corruption_detected(tmp_path):
    tree = {"a": np.arange(128, dtype=np.float32), "b": np.ones(4, np.int64)}
    save_checkpoint(tmp_path, 1, tree, fsync=False)
    shard = next((tmp_path / "step_000000000001").glob("shard_*.npz"))
    faults.corrupt_file(shard, seed=0)
    with pytest.raises(CheckpointCorruptError):
        restore_checkpoint(tmp_path, 1, template={"a": 0, "b": 0})


# ---------------------------------------------------------------------------
# snapshot / restore parity
# ---------------------------------------------------------------------------


def test_export_import_replica_round_trip(stream_data):
    src = _factory()
    for b in _batches(stream_data, 4):
        src.partial_fit(b)
    tree = export_replica(src, seq=4)
    dst = _factory()
    meta = import_replica(dst, tree)
    assert meta["seq"] == 4 and meta["backend"] == "exact"
    _assert_replica_equal(src, dst)
    # the planted serve snapshot answers without a rebuild
    q = stream_data[:16]
    np.testing.assert_array_equal(
        src.assign(q).labels, dst.assign(q).labels
    )


def test_import_replica_rejects_mismatched_operating_point(stream_data):
    src = _factory()
    src.partial_fit(stream_data[:128])
    tree = export_replica(src, seq=1)
    with pytest.raises(ValueError):
        import_replica(StreamingLAF(EPS, TAU + 1, backend="exact"), tree)
    with pytest.raises(ValueError):
        import_replica(
            StreamingLAF(EPS, TAU, backend="random_projection"), tree
        )


def test_durable_stream_is_label_identical_to_bare(stream_data, tmp_path):
    bare = _factory()
    d = DurableStream(_factory(), tmp_path, snapshot_every=2, fsync=False)
    for b in _batches(stream_data, 5):
        bare.partial_fit(b)
        d.partial_fit(b)
    _assert_replica_equal(bare, d.stream)
    d.close()


@pytest.mark.parametrize("kill_after", [1, 3, 4])
def test_kill_at_batch_boundary_bit_identical(stream_data, tmp_path, kill_after):
    batches = _batches(stream_data, 5)
    bare = _factory()
    for b in batches:
        bare.partial_fit(b)

    d = DurableStream(_factory(), tmp_path, snapshot_every=2, fsync=False)
    for b in batches[:kill_after]:
        d.partial_fit(b)
    # process dies here: no close(), no final snapshot
    d2 = DurableStream.recover(tmp_path, _factory, fsync=False)
    assert d2.seq == kill_after
    assert d2.recovery_info["wal_records"] + 0 >= 0
    for b in batches[kill_after:]:
        d2.partial_fit(b)
    _assert_replica_equal(bare, d2.stream)
    d.close()
    d2.close()


def test_kill_restore_random_projection_ari(stream_data, tmp_path):
    def rp_factory():
        return StreamingLAF(
            EPS, TAU, block_size=256, backend="random_projection"
        )

    batches = _batches(stream_data, 4)
    bare = rp_factory()
    for b in batches:
        bare.partial_fit(b)
    d = DurableStream(rp_factory(), tmp_path, snapshot_every=2, fsync=False)
    for b in batches[:3]:
        d.partial_fit(b)
    d2 = DurableStream.recover(tmp_path, rp_factory, fsync=False)
    d2.partial_fit(batches[3])
    assert adjusted_rand_index(d2.labels(), bare.labels()) >= 0.99
    d.close()
    d2.close()


def test_mid_batch_torn_tail_dropped(stream_data, tmp_path):
    batches = _batches(stream_data, 5)
    d = DurableStream(_factory(), tmp_path, snapshot_every=0, fsync=False)
    for b in batches[:3]:
        d.partial_fit(b)
    wal = d._wal.path
    d.close()
    # simulate a kill mid-append of batch 4: a torn record tail lands
    w = WalWriter(tmp_path / "scratch.log", fsync=False)
    w.append(4, KIND_INGEST, {"rows": batches[3]})
    w.close()
    rec = (tmp_path / "scratch.log").read_bytes()[8:]
    with open(wal, "ab") as f:
        f.write(rec[: len(rec) // 2])
    d2 = DurableStream.recover(tmp_path, _factory, fsync=False)
    assert d2.seq == 3  # the torn batch 4 was dropped deterministically
    ref = _factory()
    for b in batches[:3]:
        ref.partial_fit(b)
    _assert_replica_equal(ref, d2.stream)
    d2.close()


def test_corrupt_snapshot_falls_back_to_older(stream_data, tmp_path, obs_sandbox):
    batches = _batches(stream_data, 6)
    bare = _factory()
    d = DurableStream(_factory(), tmp_path, snapshot_every=2, fsync=False)
    for b in batches:
        bare.partial_fit(b)
        d.partial_fit(b)
    d.close()
    steps = list_steps(tmp_path)
    assert len(steps) >= 2
    newest = steps[-1]
    shard = next((tmp_path / f"step_{newest:012d}").glob("shard_*.npz"))
    faults.corrupt_file(shard, seed=1)
    d2 = DurableStream.recover(tmp_path, _factory, fsync=False)
    assert d2.recovery_info["snapshot_step"] < newest
    assert d2.seq == len(batches)  # WAL replay covered the gap
    _assert_replica_equal(bare, d2.stream)
    assert metrics.counter("durability.corrupt_snapshots").value >= 1
    d2.close()


def test_evict_through_wal_replay(stream_data, tmp_path):
    batches = _batches(stream_data, 4)
    evict_idx = np.arange(0, 120, 3, dtype=np.int64)
    bare = _factory()
    for b in batches[:3]:
        bare.partial_fit(b)
    bare.evict(evict_idx)
    bare.partial_fit(batches[3])

    d = DurableStream(_factory(), tmp_path, snapshot_every=2, fsync=False)
    for b in batches[:3]:
        d.partial_fit(b)
    d.evict(evict_idx)
    d2 = DurableStream.recover(tmp_path, _factory, fsync=False)
    d2.partial_fit(batches[3])
    _assert_replica_equal(bare, d2.stream)
    d.close()
    d2.close()


def test_sigkill_mid_run_then_recover(stream_data, tmp_path):
    """Real process death: the child SIGKILLs itself after 3 batches;
    recovery in this process must be bit-identical to an uninterrupted
    run over the surviving prefix + the remaining batches."""
    child = textwrap.dedent(
        """
        import os, signal, sys
        sys.path.insert(0, "src")
        import numpy as np
        from repro.data.synthetic import make_angular_clusters
        from repro.stream import DurableStream, StreamingLAF

        data, _ = make_angular_clusters(700, 16, 8, kappa=120,
                                        noise_frac=0.3, seed=7)
        data = data[np.random.default_rng(1).permutation(len(data))]
        step = -(-len(data) // 5)
        batches = [data[i:i + step] for i in range(0, len(data), step)]
        d = DurableStream(
            StreamingLAF(0.35, 5, block_size=256, backend="exact"),
            sys.argv[1], snapshot_every=2, fsync=True,
        )
        for b in batches[:3]:
            d.partial_fit(b)
        os.kill(os.getpid(), signal.SIGKILL)
        """
    )
    proc = subprocess.run(
        [sys.executable, "-c", child, str(tmp_path)],
        capture_output=True, text=True, timeout=300, cwd=".",
    )
    assert proc.returncode == -signal.SIGKILL, proc.stderr[-2000:]

    batches = _batches(stream_data, 5)
    d2 = DurableStream.recover(tmp_path, _factory, fsync=False)
    assert d2.seq == 3
    for b in batches[3:]:
        d2.partial_fit(b)
    bare = _factory()
    for b in batches:
        bare.partial_fit(b)
    _assert_replica_equal(bare, d2.stream)
    d2.close()


# ---------------------------------------------------------------------------
# failover: clone a read replica, promote after primary death
# ---------------------------------------------------------------------------


def test_failover_clone_then_promote(stream_data, tmp_path):
    batches = _batches(stream_data, 5)
    primary = DurableStream(_factory(), tmp_path, snapshot_every=2, fsync=False)
    for b in batches[:3]:
        primary.partial_fit(b)
    # clone a read replica from the published snapshot + WAL
    replica, seq, info = clone_replica(tmp_path, _factory)
    assert seq == 3 and info["recovery_s"] >= 0
    ref3 = _factory()
    for b in batches[:3]:
        ref3.partial_fit(b)
    _assert_replica_equal(ref3, replica)
    # primary writes two more batches, then dies
    for b in batches[3:]:
        primary.partial_fit(b)
    primary.close()
    promoted = DurableStream.promote(replica, tmp_path, seq, fsync=False)
    assert promoted.seq == 5
    assert promoted.recovery_info["wal_records"] == 2
    bare = _factory()
    for b in batches:
        bare.partial_fit(b)
    _assert_replica_equal(bare, promoted.stream)
    promoted.close()


def test_snapshot_gc_drops_covered_wal_files(stream_data, tmp_path):
    d = DurableStream(
        _factory(), tmp_path, snapshot_every=1, keep=2, fsync=False
    )
    for b in _batches(stream_data, 6):
        d.partial_fit(b)
    steps = list_steps(tmp_path)
    assert len(steps) <= 2
    oldest = steps[0]
    for f in tmp_path.glob("wal_*.log"):
        assert int(f.stem.split("_")[1]) >= oldest
    d.close()


# ---------------------------------------------------------------------------
# fault injection + graceful degradation
# ---------------------------------------------------------------------------


def test_fault_plan_grammar_and_determinism():
    plan = faults.FaultPlan.parse("seed=9,sweep.launch=0.5,cluster.launch=1.0:2")
    assert plan.seed == 9
    assert plan.rules["cluster.launch"].max_count == 2
    fires = [plan.should_fail("sweep.launch") for _ in range(64)]
    replay = faults.FaultPlan.parse("seed=9,sweep.launch=0.5,cluster.launch=1.0:2")
    assert fires == [replay.should_fail("sweep.launch") for _ in range(64)]
    assert 0 < sum(fires) < 64  # prob 0.5: some fire, some don't
    assert sum(plan.should_fail("cluster.launch") for _ in range(10)) == 2


# geometry deliberately disjoint from tests/test_obs.py's CFG (d=48,
# n_bits=128): these tests run before test_obs in the suite and would
# otherwise pre-warm the module-level sweep jit caches whose recompile
# count test_sweep_recompiles_once_per_capacity_doubling asserts on.
def _interp_backend(data=None):
    # degrading to the host oracle is opt-in; these tests exercise it
    bk = RandomProjectionBackend(
        device=True, interpret=True, sweep=True,
        n_bits=128, margin=3.0, seed=3, chunk=64, q_tile=32, db_tile=64,
        on_device_fault="degrade",
    )
    return bk if data is None else bk.fit(data)


@pytest.fixture(scope="module")
def small_angular():
    data, _ = make_angular_clusters(192, 48, 6, kappa=120, noise_frac=0.3, seed=2)
    return data


def test_degraded_sweep_matches_host_oracle(small_angular, obs_sandbox):
    data = small_angular
    rows = np.arange(64)
    host = RandomProjectionBackend(
        n_bits=128, margin=3.0, seed=3, chunk=64, device=False
    ).fit(data)
    bk = _interp_backend(data)
    with faults.inject("seed=5,sweep.launch=1.0"):
        counts = bk.query_counts(rows, 0.55)
        hits = bk.query_hits(rows, 0.55)
    np.testing.assert_array_equal(counts, host.query_counts(rows, 0.55))
    np.testing.assert_array_equal(hits, host.query_hits(rows, 0.55))
    assert metrics.counter("stream.degraded.events").value >= 2
    assert metrics.counter("stream.degraded.counts").value >= 1
    assert metrics.counter("stream.degraded.hits").value >= 1
    assert metrics.counter("slo.violations").value >= 1
    assert metrics.counter("faults.injected").value >= 2


def test_device_loss_sticky_breaker(small_angular, obs_sandbox):
    bk = _interp_backend(small_angular)
    rows = np.arange(32)
    with faults.inject("seed=5,sweep.launch=1.0"):
        for _ in range(3):
            bk.query_counts(rows, 0.55)
    assert bk._device_disabled
    assert not bk.use_device
    assert metrics.counter("stream.degraded.device_disabled").value == 1
    # device loss is sticky: the next query never launches (no new faults
    # are even consulted because the host path is taken outright)
    bk.query_counts(rows, 0.55)
    bk.reset_device()
    assert not bk._device_disabled


def test_on_device_fault_raise_surfaces(small_angular):
    bk = RandomProjectionBackend(
        device=True, interpret=True, sweep=True, n_bits=128, margin=3.0,
        seed=3, chunk=64, q_tile=32, db_tile=64, on_device_fault="raise",
        fault_retries=0,
    ).fit(small_angular)
    with faults.inject("seed=5,sweep.launch=1.0"):
        with pytest.raises(faults.InjectedFault):
            bk.query_counts(np.arange(16), 0.55)


def test_device_fault_raises_by_default(small_angular, obs_sandbox):
    """Degrading is opt-in: with no policy given, a device fault that
    survives its retries reaches the caller and no degraded counter moves."""
    bk = RandomProjectionBackend(
        device=True, interpret=True, sweep=True, n_bits=128, margin=3.0,
        seed=3, chunk=64, q_tile=32, db_tile=64, fault_retries=0,
    ).fit(small_angular)
    pc = np.full(len(small_angular), 10**9)
    with faults.inject("seed=5,sweep.launch=1.0"):
        with pytest.raises(faults.InjectedFault):
            bk.query_counts(np.arange(16), 0.55)
    with faults.inject("seed=3,cluster.launch=1.0"):
        with pytest.raises(RuntimeError):
            laf_dbscan(small_angular, 0.45, 4, 1.0, pc, backend="exact",
                       cluster_device=True)
    assert metrics.counter("stream.degraded.events").value == 0


def test_cluster_launch_degrades_to_host_pass(small_angular, obs_sandbox):
    data = small_angular
    pc = np.full(len(data), 10**9)
    ref = laf_dbscan(data, 0.45, 4, 1.0, pc, backend="exact",
                     cluster_device=False)
    with faults.inject("seed=3,cluster.launch=1.0"):
        deg = laf_dbscan(data, 0.45, 4, 1.0, pc, backend="exact",
                         cluster_device=True, on_device_fault="degrade")
    np.testing.assert_array_equal(ref.labels, deg.labels)
    assert metrics.counter("stream.degraded.cluster").value == 1
    assert metrics.counter("slo.violations").value >= 1
    with faults.inject("seed=3,cluster.launch=1.0"):
        with pytest.raises(RuntimeError):
            laf_dbscan(data, 0.45, 4, 1.0, pc, backend="exact",
                       cluster_device=True, on_device_fault="raise")


def test_ingest_under_faults_is_exact(small_angular, obs_sandbox):
    """Seeded launch faults during streaming ingest degrade to the host
    oracle: final labels identical (ARI 1.0) with recorded evidence."""
    data = small_angular

    def run(spec):
        bk = _interp_backend()  # fresh unfit instance
        s = StreamingLAF(0.55, 4, block_size=64, backend=bk)
        ctx = faults.inject(spec) if spec else None
        if ctx:
            with ctx:
                for i in range(0, len(data), 64):
                    s.partial_fit(data[i : i + 64])
        else:
            for i in range(0, len(data), 64):
                s.partial_fit(data[i : i + 64])
        return s.labels()

    clean = run(None)
    faulty = run("seed=11,sweep.launch=0.5")
    assert adjusted_rand_index(clean, faulty) == 1.0
    assert metrics.counter("stream.degraded.events").value >= 1
    assert metrics.counter("slo.violations").value >= 1


def test_restore_is_recompile_free():
    """laf-lint's LAF108 probe: re-querying pre-crash shapes after a
    state_export/state_import round-trip compiles nothing new."""
    from repro.analysis.jaxpr_checks import _restore_probe_findings

    assert _restore_probe_findings() == []


def test_rebuild_counter_and_event(stream_data, obs_sandbox):
    s = _factory()
    s.partial_fit(stream_data[:400])
    core_idx = np.nonzero(s.state.core[: s.state.n])[0][:40]
    s.evict(core_idx.astype(np.int64))
    assert metrics.counter("stream.rebuilds").value >= 1
    reasons = (
        metrics.counter("stream.rebuilds.core_death").value
        + metrics.counter("stream.rebuilds.tombstone_frac").value
        + metrics.counter("stream.rebuilds.manual").value
    )
    assert reasons == metrics.counter("stream.rebuilds").value
