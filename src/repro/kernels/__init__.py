"""Pallas TPU kernels for the paper's compute hot spots.

Three kernels, each a `pl.pallas_call` with explicit BlockSpec tiling, a
jit'd wrapper (ops.py) and a pure-jnp oracle (ref.py):

* ``bitplane_transpose`` — 32x32 bit-matrix butterfly transpose, the GD
  bit-plane packing hot loop (HBM-bandwidth bound, pure VPU).
* ``mshift`` — the iterative multiply&shift transform (§3.2) fused into a
  single VMEM-resident loop: all iterations without per-iteration HBM
  round-trips (the TPU-native rethink of the paper's iterate-until-captured
  loop).
* ``sharedbits`` — AND/OR reduction producing the shared-bit mask that
  drives GreedyGD base selection and the transforms' D_M choice.
* ``scoregrid`` — fused per-plane bit statistics + pooled byte histogram
  for the stacked phase-1 candidate grid.
* ``rans`` — the device-resident entropy coder behind the ``"rans"``
  container backend: Pallas encode-statistics pass + batched-jnp decode
  lane loop over an N-way interleaved byte rANS bitstream (``ref.py`` is
  the normative numpy spec).

All kernels run in interpret mode on CPU (validated against ref.py in
tests/test_kernels.py / tests/test_rans.py).  On a TPU they are compiled;
tests/test_tpu_compile.py compiles scoregrid and the rANS histogram for a
described v5e.
"""
import jax

INTERPRET_DEFAULT = jax.default_backend() != "tpu"  # CPU container: interpret
