"""Planner-aware jit'd wrappers over the Pallas kernels.

``use_kernels(plan)`` routes model-level calls either to the fused Pallas
kernels (with the planner's block sizes) or to the pure-JAX fused paths —
the runtime realisation of the evaluator's fusion decision.  Each kernel
runs compiled on a TPU and in interpret mode on the CPU backend, decided
per call (:func:`repro.kernels.interpret_default`).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from . import fused_attention, fused_conv, fused_mlp, mamba_scan
from . import ref


@partial(jax.jit, static_argnames=("causal", "window", "chunk", "block_q",
                                   "block_k"))
def attention(q, k, v, *, causal=True, window=0, chunk=0, block_q=128,
              block_k=128):
    return fused_attention.flash_attention(
        q, k, v, causal=causal, window=window, chunk=chunk,
        block_q=block_q, block_k=block_k,
    )


@partial(jax.jit, static_argnames=("act", "block_m", "block_f"))
def mlp(x, w1, w2, w3=None, *, act="swiglu", block_m=128, block_f=512):
    return fused_mlp.fused_mlp(
        x, w1, w2, w3, act=act, block_m=block_m, block_f=block_f,
    )


@partial(jax.jit, static_argnames=("pool", "block_c"))
def conv3x3(x, w, b, *, pool=False, block_c=128):
    return fused_conv.fused_conv3x3(x, w, b, pool=pool, block_c=block_c)


@partial(jax.jit, static_argnames=("chunk", "block_d"))
def ssm_scan(dA, dBx, C, *, chunk=64, block_d=512):
    return mamba_scan.selective_scan(dA, dBx, C, chunk=chunk, block_d=block_d)


def fused_conv_fn(plan=None):
    """Adapter for repro.models.vgg.forward(fused_conv_fn=...)."""
    block_c = plan.conv_block_c if plan is not None else 128

    def fn(x, w, b, *, pool):
        return conv3x3(x, w, b, pool=pool, block_c=block_c)

    return fn


REFS = {
    "attention": ref.flash_attention_ref,
    "mlp": ref.fused_mlp_ref,
    "conv3x3": ref.fused_conv3x3_ref,
    "ssm_scan": ref.selective_scan_ref,
}
