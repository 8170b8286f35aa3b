"""Fused conv3x3 + bias + ReLU (+ 2x2 max-pool) — the paper's own workload.

This is the kernel the paper's DLA executes (Fig. 1: PE array + the inline
ReLU/BN/pool functional unit).  One fusion group = conv + activation +
pool: the pre-pool output frame (Noh x Now x M, the ``out_words_prepool``
quantity in the evaluator's area model) stays in VMEM; only the pooled
frame is written to HBM — the exact traffic the evaluator's Eq. (1)
credits a fused group.

TPU adaptation of the 3x3 systolic dataflows in [2][3]: the 3x3 window is
decomposed into 9 shifted (H*W, Cin) x (Cin, Cout-block) MXU matmuls (the
MXU replaces the PE adder trees; F1..F4 become grid/block factors).  VGG
feature maps (<= 224x224x64 = 6.4 MiB bf16) fit whole in VMEM, so the grid
is (batch, Cout/block_c) with full-frame blocks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import VMEM_LIMIT_BYTES, interpret_default, tile_bytes


def _kernel(x_ref, w_ref, b_ref, o_ref, *scratch, H, W, pool):
    # x_ref holds the zero-padded (H+2, W+2, Cin) frame; each of the 9 taps
    # is a static window of it, read straight from VMEM.
    Cin = x_ref.shape[-1]
    bc = w_ref.shape[-1]
    acc = jnp.zeros((H * W, bc), jnp.float32)
    for dy in range(3):
        for dx in range(3):
            patch = x_ref[0, dy : dy + H, dx : dx + W, :].astype(jnp.float32)
            acc += jax.lax.dot_general(
                patch.reshape(H * W, Cin),
                w_ref[dy, dx].astype(jnp.float32),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
    b = b_ref[...].astype(jnp.float32)  # (1, bc)
    y = jnp.maximum(acc + b, 0.0).reshape(H, W, bc)
    if pool:  # fused 2x2 max pool: pre-pool frame never leaves VMEM
        (rows_sc,) = scratch
        rows_sc[...] = y.reshape(H // 2, 2, W, bc).max(axis=1)
        y = jnp.maximum(
            rows_sc[:, pl.ds(0, W // 2, stride=2), :],
            rows_sc[:, pl.ds(1, W // 2, stride=2), :],
        )
    o_ref[0] = y.astype(o_ref.dtype)


def fused_conv3x3(
    x: jnp.ndarray,  # (B, H, W, Cin)
    w: jnp.ndarray,  # (3, 3, Cin, Cout)
    b: jnp.ndarray,  # (Cout,)
    *,
    pool: bool = False,
    block_c: int = 128,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """3x3 SAME conv + bias + ReLU (+ 2x2 max-pool) over Cout blocks.
    ``interpret=None`` interprets on the CPU backend and compiles
    elsewhere (:func:`repro.kernels.interpret_default`)."""
    if interpret is None:
        interpret = interpret_default(x, w, b)
    B, H, W, Cin = x.shape
    Cout = w.shape[-1]
    block_c = min(block_c, Cout)
    assert Cout % block_c == 0
    Ho, Wo = (H // 2, W // 2) if pool else (H, W)
    xp = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))

    kernel = functools.partial(_kernel, H=H, W=W, pool=pool)
    return pl.pallas_call(
        kernel,
        grid=(B, Cout // block_c),
        in_specs=[
            pl.BlockSpec((1, H + 2, W + 2, Cin), lambda ib, jc: (ib, 0, 0, 0)),
            pl.BlockSpec((3, 3, Cin, block_c), lambda ib, jc: (0, 0, 0, jc)),
            pl.BlockSpec((1, block_c), lambda ib, jc: (0, jc)),
        ],
        out_specs=pl.BlockSpec((1, Ho, Wo, block_c), lambda ib, jc: (ib, 0, 0, jc)),
        out_shape=jax.ShapeDtypeStruct((B, Ho, Wo, Cout), x.dtype),
        scratch_shapes=(
            [pltpu.VMEM((H // 2, W, block_c), jnp.float32)] if pool else []
        ),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES),
    )(xp, w, b.reshape(1, Cout))


def vmem_bytes(H: int, W: int, Cin: int, block_c: int, dtype_bytes: int = 2) -> int:
    """VMEM working set: the pipelined padded frame, weight and output
    blocks twice, one f32 tap, and the f32 pre-pool accumulator."""
    tiles = (
        tile_bytes((H + 2, W + 2, Cin), dtype_bytes)  # padded input frame
        + tile_bytes((9 * Cin, block_c), dtype_bytes)  # weights
        + tile_bytes((H, W, block_c), dtype_bytes)  # out tile
    )
    return (
        2 * tiles
        + tile_bytes((H * W, Cin), 4)  # one f32 tap
        + tile_bytes((H * W, block_c), 4)  # pre-pool accumulator
    )
