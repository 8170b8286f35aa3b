"""Selective-scan (Mamba-1 recurrence) as a Pallas TPU kernel.

Fusion group: discretisation already done upstream; this kernel fuses the
recurrence ``h_t = dA_t * h + dBx_t`` with the readout ``y_t = <h_t, C_t>``
so the (S, d_inner, d_state) transition tensors stream through VMEM chunk
by chunk and the (d_inner, d_state) state never leaves VMEM between steps
— 128x HBM-traffic reduction vs. materialising the state sequence for
falcon-mamba's d_inner=8192, d_state=16.

Grid: ``(B, d_inner/block_d, S/chunk)`` with the sequence axis innermost
and sequential; the state carry lives in VMEM scratch, zero-initialised at
chunk 0.  In-chunk steps run as a fori_loop that reads step ``t`` of each
block straight from VMEM and writes ``y_t`` in place (the associative-scan
variant is the chunked pure-JAX path in repro.models.ssm).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import VMEM_LIMIT_BYTES, interpret_default, tile_bytes


def _kernel(dA_ref, dBx_ref, c_ref, y_ref, h_sc, *, chunk):
    js = pl.program_id(2)

    @pl.when(js == 0)
    def _init():
        h_sc[...] = jnp.zeros_like(h_sc)

    def step(t, h):
        h = dA_ref[0, t].astype(jnp.float32) * h + dBx_ref[0, t].astype(
            jnp.float32
        )  # (bd, ds)
        c_t = c_ref[0, pl.ds(t, 1), :].astype(jnp.float32)  # (1, ds)
        y_ref[0, pl.ds(t, 1), :] = jnp.sum(h * c_t, axis=1)[None, :].astype(
            y_ref.dtype
        )
        return h

    h_sc[...] = jax.lax.fori_loop(0, chunk, step, h_sc[...])


def selective_scan(
    dA: jnp.ndarray,  # (B, S, di, ds) f32
    dBx: jnp.ndarray,  # (B, S, di, ds) f32
    C: jnp.ndarray,  # (B, S, ds) f32
    *,
    chunk: int = 64,
    block_d: int = 512,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Returns y (B, S, di) f32.  ``interpret=None`` interprets on the CPU
    backend and compiles elsewhere (:func:`repro.kernels.interpret_default`)."""
    if interpret is None:
        interpret = interpret_default(dA, dBx, C)
    B, S, di, ds = dA.shape
    chunk = min(chunk, S)
    block_d = min(block_d, di)
    assert S % chunk == 0 and di % block_d == 0
    ns, nd = S // chunk, di // block_d

    kernel = functools.partial(_kernel, chunk=chunk)
    out = pl.pallas_call(
        kernel,
        grid=(B, nd, ns),
        in_specs=[
            pl.BlockSpec((1, chunk, block_d, ds), lambda b, jd, js: (b, js, jd, 0)),
            pl.BlockSpec((1, chunk, block_d, ds), lambda b, jd, js: (b, js, jd, 0)),
            pl.BlockSpec((1, chunk, ds), lambda b, jd, js: (b, js, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, block_d), lambda b, jd, js: (b, js, jd)),
        out_shape=jax.ShapeDtypeStruct((B, S, di), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_d, ds), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES),
    )(dA, dBx, C)
    return out


def vmem_bytes(chunk: int, block_d: int, ds: int) -> int:
    """VMEM working set: the pipelined dA/dBx/C/y blocks twice (the
    d_state minor dim pads to 128 lanes) plus the state scratch."""
    tiles = (
        2 * tile_bytes((chunk, block_d, ds), 4)  # dA, dBx tiles
        + tile_bytes((chunk, ds), 4)  # C tile
        + tile_bytes((chunk, block_d), 4)  # y tile
    )
    return 2 * tiles + tile_bytes((block_d, ds), 4)  # + state scratch
