"""Pallas TPU kernels for the fusion groups the planner selects.

Each kernel keeps a fusion group's intermediate tensors in VMEM (the TPU
analogue of the paper's on-chip SRAM): the flash-attention score tile, the
SwiGLU hidden activations, the conv3x3 pre-pool frame, and the selective
scan's discretised transitions never round-trip through HBM.

``ops.py`` holds the jit'd dispatch wrappers (planner-aware), ``ref.py``
the pure-jnp oracles every kernel is validated against (see
tests/test_kernels.py shape/dtype sweeps, and tests/test_chip_compile.py
for the compiles at planner-chosen blocks for a described v5e).
"""
from __future__ import annotations

import math

import jax

#: Scoped VMEM each kernel may claim: the planner's budget, a quarter of
#: the v5e core's 128 MiB (``repro.core.arch.TPU_V5E.vmem_bytes // 4``).
#: The compiler's default scoped limit is smaller, so every kernel asks
#: for this one explicitly.
VMEM_LIMIT_BYTES = 32 * 1024 * 1024


def interpret_default(*arrays) -> bool:
    """Pallas interpret mode iff the operands live on the CPU backend.

    Decided per call: a concrete array answers from its own devices; a
    tracer (inside ``jit``) or a host array from the backend JAX places
    it on by default.  A kernel on a TPU therefore always runs compiled.
    """
    for a in arrays:
        if isinstance(a, jax.Array) and not isinstance(a, jax.core.Tracer):
            return all(d.platform == "cpu" for d in a.devices())
    return jax.default_backend() == "cpu"


def tile_bytes(shape, itemsize: int) -> int:
    """VMEM bytes of one buffer of ``shape`` in the TPU's native tiling:
    the minor dim padded to 128 lanes, the second-minor to 8 rows of
    32-bit words (16 for 2-byte types)."""
    *lead, rows, lanes = (1,) * max(0, 2 - len(shape)) + tuple(shape)
    sub = 8 * max(1, 4 // itemsize)
    rows = -(-rows // sub) * sub
    lanes = -(-lanes // 128) * 128
    return math.prod(lead) * rows * lanes * itemsize
