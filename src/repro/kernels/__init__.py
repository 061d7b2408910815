"""Pallas TPU kernels for the perf-critical compute layers.

Each kernel lives in its own subpackage:
  kernel.py -- pl.pallas_call + explicit BlockSpec VMEM tiling (TPU target)
  ops.py    -- jit'd public wrapper (padding, dtype policy, interpret switch)
  ref.py    -- pure-jnp oracle the kernel is validated against

On a TPU the wrappers run the compiled kernels; on the CPU (tests, with
``JAX_PLATFORMS=cpu``) they run in the Pallas interpreter.  BlockSpecs
and grids follow the TPU (8, 128) block rule and v5e VMEM budgets;
``tests/test_tpu_compile.py`` compiles the main-path kernels for a
described v5e chip, and ``chip_smoke.py`` runs them on one.
"""
