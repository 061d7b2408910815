"""Compile the main-path kernels and programs for a described TPU v5e.

Nothing runs: each test lowers and compiles for a ``v5e:2x2`` topology
described (not attached) through the installed TPU compiler, at the
MS-150k widths the on-chip smoke run uses — d = 768, 512-bit signatures
(16 words), 152,185 database rows.  A Pallas kernel that interpret-mode
parity accepts can still be refused by the chip's compiler (block
shapes, register layouts, memory spaces); these tests catch that here.

The topology is described inside a module fixture, never at import, so
pytest-xdist workers all collect the same tests and only the worker that
runs this file loads the TPU library.
"""

import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

N, D, WORDS = 152_185, 768, 16
CHUNK, CPL = 256, 8                      # sweep chunk rows, chunks per launch
DB_ROWS = -(-N // 256) * 256             # db padded to the kernel db tile
SLAB_WORDS = DB_ROWS // 32
R_EXEC = 48 * CHUNK * CPL                # ~99k executed rows, launch-padded
V5E_HBM = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        t = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache, so keep it out of the cache entirely
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    return Mesh(np.asarray(topo.devices[:4]), ("data",))


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()  # the Pallas kernel is in
    return compiled


def _bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


@pytest.mark.parametrize("with_bitmap", [False, True], ids=["count", "bitmap"])
@pytest.mark.parametrize("with_stats", [False, True], ids=["plain", "stats"])
def test_hamming_filter_builds_compile(one_chip, with_bitmap, with_stats):
    from repro.kernels.hamming_filter.kernel import hamming_filter_pallas

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    _compile(
        lambda q, db, qs, dbs: hamming_filter_pallas(
            q, db, qs, dbs, 0.55, 147, 213,
            with_bitmap=with_bitmap, with_stats=with_stats,
        ),
        s((CHUNK, D), jnp.float32), s((DB_ROWS, D), jnp.float32),
        s((CHUNK, WORDS), jnp.uint32), s((DB_ROWS, WORDS), jnp.uint32),
    )


@pytest.mark.parametrize("kernel", ["label_prop_rect", "col_reduce"])
def test_label_prop_kernels_compile(one_chip, kernel):
    from repro.kernels.label_prop.kernel import col_reduce_pallas, label_prop_rect_pallas

    w = -(-SLAB_WORDS // 128) * 128
    slab = jax.ShapeDtypeStruct((2048, w), jnp.uint32, sharding=one_chip)
    vec = jax.ShapeDtypeStruct((2048,), jnp.int32, sharding=one_chip)
    if kernel == "label_prop_rect":
        cols = jax.ShapeDtypeStruct((w * 32,), jnp.int32, sharding=one_chip)
        _compile(lambda r, c, b: label_prop_rect_pallas(r, c, b), vec, cols, slab)
    else:
        _compile(lambda b, v, wt: col_reduce_pallas(b, v, wt), slab, vec, vec)


def test_sweep_launch_program_compiles(one_chip):
    """The one-launch bitmap sweep: a fori_loop of 8 kernel chunks
    writing into the donated (R, W) slab."""
    from repro.index.sweep import _bitmap_launch_donated

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = (
        _bitmap_launch_donated.trace(
            s((R_EXEC,), jnp.int32), s((R_EXEC, SLAB_WORDS), jnp.uint32),
            s((R_EXEC // CHUNK, 3), jnp.int32), s((), jnp.int32),
            s((CHUNK * CPL, D), jnp.float32), s((CHUNK * CPL, WORDS), jnp.uint32),
            s((DB_ROWS, D), jnp.float32), s((DB_ROWS, WORDS), jnp.uint32),
            s((1,), jnp.float32), s((2,), jnp.int32),
            chunk=CHUNK, q_tile=128, db_tile=256, interpret=False,
        )
        .lower()
        .compile()
    )
    assert "tpu_custom_call" in compiled.as_text()
    assert _bytes(compiled) < V5E_HBM


def test_packed_cluster_program_compiles(one_chip):
    """The single-device while-loop cluster program over the full slab."""
    from repro.kernels.label_prop.ops import _packed_cluster_jit

    compiled = _compile(
        lambda b, r, t: _packed_cluster_jit(
            b, r, t, n=N, max_iters=64, row_tile=256, word_tile=128,
            interpret=False,
        ),
        jax.ShapeDtypeStruct((R_EXEC, SLAB_WORDS), jnp.uint32, sharding=one_chip),
        jax.ShapeDtypeStruct((R_EXEC,), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
    )
    assert _bytes(compiled) < V5E_HBM


def test_sharded_sweep_program_compiles_4chip(mesh4):
    from repro.distributed.index_plane import _build_sweep_plane_fn, shard_plan

    plan = shard_plan(mesh4, N, tile=4096)
    rep = NamedSharding(mesh4, P())
    rows = NamedSharding(mesh4, P("data", None))
    fn = _build_sweep_plane_fn(mesh4, ("data",), "bitmap", CHUNK, 128, 256, False, 2)
    compiled = fn.lower(
        jax.ShapeDtypeStruct((CHUNK * CPL, D), jnp.float32, sharding=rep),
        jax.ShapeDtypeStruct((CHUNK * CPL, WORDS), jnp.uint32, sharding=rep),
        jax.ShapeDtypeStruct((plan.n_padded, D), jnp.float32, sharding=rows),
        jax.ShapeDtypeStruct((plan.n_padded, WORDS), jnp.uint32, sharding=rows),
        jax.ShapeDtypeStruct((1,), jnp.float32, sharding=rep),
        jax.ShapeDtypeStruct((2,), jnp.int32, sharding=rep),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_cluster_plane_program_compiles_4chip(mesh4):
    from repro.distributed.index_plane import _build_cluster_plane_fn, shard_plan

    plan = shard_plan(mesh4, N, tile=4096)
    rep = NamedSharding(mesh4, P())
    fn = _build_cluster_plane_fn(mesh4, ("data",), N, 64, 256, 128, False, False)
    compiled = fn.lower(
        jax.ShapeDtypeStruct(
            (R_EXEC, plan.n_padded // 32), jnp.uint32,
            sharding=NamedSharding(mesh4, P(None, "data")),
        ),
        jax.ShapeDtypeStruct((R_EXEC,), jnp.int32, sharding=rep),
        jax.ShapeDtypeStruct((1,), jnp.int32, sharding=rep),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _bytes(compiled) < V5E_HBM
