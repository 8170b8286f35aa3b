"""Fused SwiGLU/GeGLU MLP as a Pallas TPU kernel.

Fusion group: ``x @ w1 -> act -> * (x @ w3) -> @ w2`` in one pass.  The
(tokens, d_ff) hidden activation — 4x the residual stream for the assigned
archs, e.g. 1 GiB/layer/device for granite's d_ff=24576 at train_4k — is
the fusion group's internal frame: it exists only as (block_m, block_f)
VMEM tiles.  HBM traffic per layer drops from
``2*T*ff + T*(2d+ff)`` words to ``T*2d + (weights)``, the Eq. (1)
bandwidth win for this group.

Grid: ``(T/block_m, ff/block_f)`` with the d_ff axis innermost; the output
(block_m, d) f32 tile accumulates partial ``h_blk @ w2_blk`` products in
VMEM scratch across d_ff steps.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import VMEM_LIMIT_BYTES, interpret_default, tile_bytes


def _kernel(x_ref, w1_ref, w3_ref, w2_ref, o_ref, acc_sc, *, n_fblocks, act):
    jf = pl.program_id(1)

    @pl.when(jf == 0)
    def _init():
        acc_sc[...] = jnp.zeros_like(acc_sc)

    x = x_ref[...].astype(jnp.float32)  # (bm, d)
    w1 = w1_ref[...].astype(jnp.float32)  # (d, bf)
    h = jax.lax.dot_general(x, w1, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if act == "swiglu":
        w3 = w3_ref[...].astype(jnp.float32)
        g = jax.lax.dot_general(x, w3, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        h = jax.nn.silu(h) * g
    elif act == "geglu":
        w3 = w3_ref[...].astype(jnp.float32)
        g = jax.lax.dot_general(x, w3, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        h = jax.nn.gelu(h) * g
    elif act == "gelu":
        h = jax.nn.gelu(h)
    else:
        h = jax.nn.relu(h)
    w2 = w2_ref[...].astype(jnp.float32)  # (bf, d)
    acc_sc[...] += jax.lax.dot_general(h, w2, (((1,), (0,)), ((), ())),
                                       preferred_element_type=jnp.float32)

    @pl.when(jf == n_fblocks - 1)
    def _finalize():
        o_ref[...] = acc_sc[...].astype(o_ref.dtype)


def fused_mlp(
    x: jnp.ndarray,  # (T, d)
    w1: jnp.ndarray,  # (d, ff)
    w2: jnp.ndarray,  # (ff, d)
    w3: jnp.ndarray | None = None,  # (d, ff) for gated acts
    *,
    act: str = "swiglu",
    block_m: int = 128,
    block_f: int = 512,
    interpret: bool | None = None,
) -> jnp.ndarray:
    if interpret is None:
        interpret = interpret_default(x, w1, w2)
    T, d = x.shape
    ff = w1.shape[1]
    block_m = min(block_m, T)
    block_f = min(block_f, ff)
    assert T % block_m == 0 and ff % block_f == 0
    nm, nf = T // block_m, ff // block_f
    if w3 is None:
        w3 = w1  # placeholder operand (unused for non-gated acts)

    kernel = functools.partial(_kernel, n_fblocks=nf, act=act)
    return pl.pallas_call(
        kernel,
        grid=(nm, nf),
        in_specs=[
            pl.BlockSpec((block_m, d), lambda im, jf: (im, 0)),
            pl.BlockSpec((d, block_f), lambda im, jf: (0, jf)),
            pl.BlockSpec((d, block_f), lambda im, jf: (0, jf)),
            pl.BlockSpec((block_f, d), lambda im, jf: (jf, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, d), lambda im, jf: (im, 0)),
        out_shape=jax.ShapeDtypeStruct((T, d), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, d), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES),
    )(x, w1, w3, w2)


def vmem_bytes(block_m: int, block_f: int, d: int, dtype_bytes: int = 2) -> int:
    """VMEM working set at these blocks: the pipelined x/w1/w3/w2/out
    tiles twice (double buffering), the f32 accumulator, and the f32
    hidden tiles h and g."""
    tiles = (
        2 * tile_bytes((block_m, d), dtype_bytes)  # x, out tiles
        + 2 * tile_bytes((d, block_f), dtype_bytes)  # w1, w3 tiles
        + tile_bytes((block_f, d), dtype_bytes)  # w2 tile
    )
    return (
        2 * tiles
        + tile_bytes((block_m, d), 4)  # accumulator
        + 2 * tile_bytes((block_m, block_f), 4)  # h, g
    )
