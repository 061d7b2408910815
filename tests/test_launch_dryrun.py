"""Launch-layer contract tests: build_cell -> jit(in/out shardings) ->
lower -> compile on a small 8-host-device mesh, in a subprocess (the
device count is locked at first jax init, so the main test process must
stay at 1 device)."""

import json
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, sys
    sys.path.insert(0, "src")
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    import dataclasses
    import repro.launch.steps as S
    from repro.configs.registry import get_arch

    from repro.launch.mesh import make_test_mesh

    mesh = make_test_mesh(8)

    # reduced configs so the 8-device compile stays fast; the production
    # builders are exercised unchanged (same sharding rules/step fns)
    results = {}

    def tiny_lm():
        arch = get_arch("llama3-8b")
        cfg = arch.make_reduced_config()
        shape = dataclasses.replace(
            arch.shapes["train_4k"], meta={"seq_len": 64, "global_batch": 8}
        )
        return dataclasses.replace(arch, make_config=lambda: cfg), shape

    arch, shape = tiny_lm()
    cell = S.build_lm_train(arch, shape, mesh)
    with mesh:
        compiled = jax.jit(
            cell.step_fn, in_shardings=cell.in_shardings,
            out_shardings=cell.out_shardings,
        ).lower(*cell.args).compile()
    results["lm_train"] = {
        "mem": int(compiled.memory_analysis().temp_size_in_bytes),
        "ok": True,
    }

    # recsys forward cell (reduced)
    arch = get_arch("deepfm")
    red = arch.make_reduced_config()
    arch = dataclasses.replace(arch, make_config=lambda: red)
    shape = dataclasses.replace(arch.shapes["serve_p99"], meta={"batch": 16})
    cell = S.build_recsys_forward(arch, shape, mesh)
    with mesh:
        jax.jit(cell.step_fn, in_shardings=cell.in_shardings,
                out_shardings=cell.out_shardings).lower(*cell.args).compile()
    results["recsys_forward"] = {"ok": True}

    # LAF cluster cell (reduced)
    arch = get_arch("laf_dbscan")
    red = arch.make_reduced_config()
    arch = dataclasses.replace(arch, make_config=lambda: red)
    shape = dataclasses.replace(
        arch.shapes["nyt_150k"], meta={"n_points": 2048, "dim": 64}
    )
    cell = S.build_laf_cluster(arch, shape, mesh)
    with mesh:
        jax.jit(cell.step_fn, in_shardings=cell.in_shardings,
                out_shardings=cell.out_shardings).lower(*cell.args).compile()
    results["laf_cluster"] = {"ok": True}

    # LAF cluster cell, random_projection backend through the sharded
    # index plane (index_device=True forces the shard_mapped tile on the
    # 8-device two-axis mesh; compiles the shard_map + psum lowering)
    red_rp = dataclasses.replace(
        red, backend="random_projection", index_device=True
    )
    arch_rp = dataclasses.replace(arch, make_config=lambda: red_rp)
    cell = S.build_laf_cluster(arch_rp, shape, mesh)
    assert cell.meta["fused_kernel"] and cell.meta["sharded"]
    assert cell.meta["n_shards"] == 8
    with mesh:
        jax.jit(cell.step_fn, in_shardings=cell.in_shardings,
                out_shardings=cell.out_shardings).lower(*cell.args).compile()
    results["laf_cluster_sharded"] = {"ok": True}

    print("RESULT:" + json.dumps(results))
    """
)


@pytest.mark.dryrun
def test_build_cells_compile_on_8_devices():
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        capture_output=True, text=True, timeout=480, cwd=".",
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT:")][-1]
    results = json.loads(line[len("RESULT:"):])
    assert results["lm_train"]["ok"]
    assert results["recsys_forward"]["ok"]
    assert results["laf_cluster"]["ok"]
    assert results["laf_cluster_sharded"]["ok"]


def test_hlo_analysis_loop_correction():
    """The loop-aware analyzer multiplies while bodies by trip count."""
    hlo = textwrap.dedent(
        """
        %body (p: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
          %p = (s32[], f32[8,8]) parameter(0)
          %i = s32[] get-tuple-element(%p), index=0
          %x = f32[8,8] get-tuple-element(%p), index=1
          %d = f32[8,8] dot(%x, %x), lhs_contracting_dims={1}, rhs_contracting_dims={0}
          ROOT %t = (s32[], f32[8,8]) tuple(%i, %d)
        }

        %cond (p: (s32[], f32[8,8])) -> pred[] {
          %p = (s32[], f32[8,8]) parameter(0)
          ROOT %ok = pred[] constant(true)
        }

        ENTRY %main (a: f32[8,8]) -> (s32[], f32[8,8]) {
          %a = f32[8,8] parameter(0)
          %z = s32[] constant(0)
          %init = (s32[], f32[8,8]) tuple(%z, %a)
          ROOT %w = (s32[], f32[8,8]) while(%init), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"5"}}
        }
        """
    )
    from repro.launch.hlo_analysis import analyze_hlo

    a = analyze_hlo(hlo)
    # one 8x8x8 dot (1024 flops) x 5 trips
    assert a.flops == pytest.approx(5 * 2 * 8 * 8 * 8)
    assert a.n_while_loops == 1


def test_roofline_row_classification():
    from repro.launch.roofline import roofline_row

    rec = {
        "status": "ok", "arch": "x", "shape": "y", "mesh": "m", "n_devices": 256,
        "meta": {"kind": "train", "tokens_per_step": 1024,
                 "active_param_count": 1_000_000, "param_count": 1_000_000},
        "hlo_analysis": {
            "flops": 1e12, "bytes_accessed": 1e12,
            "collectives": {"total": {"bytes": 1e9}},
        },
        "memory_analysis": {"bytes_per_device": {"total": 2**30}},
    }
    row = roofline_row(rec)
    assert row.bound == "memory"          # 1e12/819e9 > 1e12/197e12, 1e9/50e9
    assert 0 < row.roofline_fraction < 1
    assert row.flops_ratio == pytest.approx(6 * 1e6 * 1024 / 256 / 1e12)


def test_peaks_table_keyed_by_device_kind():
    from repro.launch.roofline import PEAKS, peaks

    assert peaks("TPU v5 lite") == PEAKS["TPU v5 lite"]
    assert peaks("TPU v5 lite")["hbm_bw"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks("cpu")


def test_compile_cache_default_dir_is_fixed_in_checkout(monkeypatch):
    import jax

    from repro.launch import compile_cache

    monkeypatch.delenv(compile_cache.CACHE_ENV, raising=False)
    prev = jax.config.jax_compilation_cache_dir
    try:
        got = compile_cache.enable_compile_cache()
        assert got == str(compile_cache.DEFAULT_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == got
        assert (compile_cache.DEFAULT_CACHE_DIR.parent / "src" / "repro").is_dir()
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_compile_cache_env_dir_receives_entries(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set the helper sets nothing, and a
    compile lands in that directory (fresh interpreter: JAX reads the
    variable at import)."""
    script = textwrap.dedent(
        """
        import sys
        sys.path.insert(0, "src")
        import jax, jax.numpy as jnp
        from repro.launch.compile_cache import enable_compile_cache

        before = jax.config.jax_compilation_cache_dir
        got = enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == before == got, (before, got)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.jit(lambda x: jnp.sin(x) * 3)(jnp.ones(8)).block_until_ready()
        print("RESULT:" + got)
        """
    )
    import os

    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cc"))
    proc = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        timeout=240, cwd=".", env=env,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert any((tmp_path / "cc").iterdir())
