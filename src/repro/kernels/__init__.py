"""Pallas TPU kernels for the perf-critical compute layers.

Each kernel package has:
  kernel.py — pl.pallas_call + explicit BlockSpec VMEM tiling (TPU target)
  ops.py    — jit'd public wrapper (layout adaptation)
  ref.py    — pure-jnp oracle used by tests/benchmarks

Every op lowers through Mosaic unless the caller passes interpret=True:
a Pallas call on a host without a TPU fails instead of silently running
the interpreter. Tests pass interpret=True explicitly; tests/
test_tpu_compile.py compiles the serving-path kernels for a described
v5e chip at published widths.
"""
