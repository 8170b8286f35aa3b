"""Flash attention forward as a Pallas TPU kernel.

Fusion group: QK^T -> mask -> online softmax -> PV.  The (block_q, block_k)
score tile lives in VMEM/VREGs only — the (Sq, Skv) "intermediate frame"
(the paper's Eq. (1) group-internal tensor) never touches HBM, cutting the
attention HBM traffic from O(Sq*Skv) to O(Sq*hd + Skv*hd).

Grid: ``(batch*heads, Sq/block_q, Skv/block_k)`` with the KV axis innermost
and sequential; the running (m, l, acc) state persists in VMEM scratch
across KV steps.  GQA is handled in the index maps (q head h reads kv head
h // group).  Masking supports causal / sliding-window / chunked-local via
absolute position arithmetic (full-block skipping is a real-TPU grid-
pruning optimisation; here blocks are masked).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import VMEM_LIMIT_BYTES, interpret_default, tile_bytes

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc, *,
            block_q, block_k, n_kblocks, causal, window, chunk, scale):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    q = q_ref[0].astype(jnp.float32)  # (block_q, hd)
    k = k_ref[0].astype(jnp.float32)  # (block_k, hd)
    v = v_ref[0].astype(jnp.float32)

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # (block_q, block_k)

    q_idx = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    k_idx = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    ok = jnp.ones(s.shape, jnp.bool_)
    if causal:
        ok &= k_idx <= q_idx
    if window > 0:
        ok &= (q_idx - k_idx) < window
    if chunk > 0:
        ok &= (q_idx // chunk) == (k_idx // chunk)
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_sc[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_new = l_sc[...] * corr + p.sum(axis=1)
    acc_new = acc_sc[...] * corr[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_sc[...] = m_new
    l_sc[...] = l_new
    acc_sc[...] = acc_new

    @pl.when(ik == n_kblocks - 1)
    def _finalize():
        o_ref[0] = (acc_sc[...] / jnp.maximum(l_sc[...], 1e-30)[:, None]).astype(
            o_ref.dtype
        )


def flash_attention(
    q: jnp.ndarray,  # (B, Sq, H, hd)
    k: jnp.ndarray,  # (B, Skv, KV, hd)
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: int = 0,  # sliding window (attn_local); 0 = off
    chunk: int = 0,  # chunked-local (attn_chunked); 0 = off
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Pallas flash attention.  Requires Sq % block_q == Skv % block_k == 0.

    ``interpret=None`` interprets on the CPU backend and compiles
    elsewhere (:func:`repro.kernels.interpret_default`)."""
    if interpret is None:
        interpret = interpret_default(q, k, v)
    B, Sq, H, hd = q.shape
    Skv, KV = k.shape[1], k.shape[2]
    G = H // KV
    block_q = min(block_q, Sq)
    block_k = min(block_k, Skv)
    assert Sq % block_q == 0 and Skv % block_k == 0
    nq, nk = Sq // block_q, Skv // block_k

    qt = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, hd)
    kt = k.transpose(0, 2, 1, 3).reshape(B * KV, Skv, hd)
    vt = v.transpose(0, 2, 1, 3).reshape(B * KV, Skv, hd)

    def q_map(bh, iq, ik):
        return (bh, iq, 0)

    def kv_map(bh, iq, ik):
        b = bh // H
        h = bh % H
        return (b * KV + h // G, ik, 0)

    kernel = functools.partial(
        _kernel, block_q=block_q, block_k=block_k, n_kblocks=nk,
        causal=causal, window=window, chunk=chunk, scale=1.0 / math.sqrt(hd),
    )
    out = pl.pallas_call(
        kernel,
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), q_map),
            pl.BlockSpec((1, block_k, hd), kv_map),
            pl.BlockSpec((1, block_k, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), q_map),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES),
    )(qt, kt, vt)
    return out.reshape(B, H, Sq, hd).transpose(0, 2, 1, 3)


def vmem_bytes(block_q: int, block_k: int, hd: int, dtype_bytes: int = 2) -> int:
    """VMEM working set at these blocks (planner feasibility): the
    pipelined q/k/v/out tiles twice (double buffering), plus the f32
    score tile and the (m, l, acc) scratch, all in native tiling."""
    tiles = (
        2 * tile_bytes((block_q, hd), dtype_bytes)  # q, out blocks
        + 2 * tile_bytes((block_k, hd), dtype_bytes)  # k, v blocks
    )
    return (
        2 * tiles
        + tile_bytes((block_q, block_k), 4)  # score tile
        + 2 * tile_bytes((1, block_q), 4)  # m, l scratch
        + tile_bytes((block_q, hd), 4)  # acc scratch
    )
