"""shard_map train step with explicit cross-pod gradient compression.

The pjit path (runtime.steps) lets GSPMD place every collective; that is
the right default, but it cannot express *mixed-precision collectives* —
int8 on the slow cross-pod links, full precision inside a pod.  This
variant computes per-pod mean gradients under ``jax.shard_map`` over the
``pod`` axis (GSPMD still handles data/model sharding *inside* each pod
via nested pjit semantics) and then reduces across pods with
``compressed_psum`` + error feedback.

Wire math for jamba train_4k on 2 pods: grads are ~398 B half-words; fp32
cross-pod all-reduce moves 1.59 TB/step on the pod links, int8 moves
0.40 TB — a 4x cut of the slowest collective term (EXPERIMENTS.md §Perf).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..models import model as M
from ..optim import AdamWConfig, adamw_update, warmup_cosine
from ..parallel.compression import compressed_psum, ef_apply


def make_compressed_train_step(cfg, rc, mesh, opt_cfg: AdamWConfig | None = None):
    """Train step with int8 error-feedback gradient sync over the pod axis.

    opt/params replicated across pods, batch split across pods; the error
    feedback buffers ride in ``opt_state["ef"]``.
    """
    assert "pod" in mesh.axis_names, "compressed sync needs a pod axis"
    opt_cfg = opt_cfg or AdamWConfig(
        weight_decay=rc.weight_decay, grad_clip=rc.grad_clip,
        state_dtype=rc.opt_state_dtype,
    )

    def loss(p, mb):
        return M.loss_fn(p, cfg, rc, mb)[0]

    grad_fn = jax.value_and_grad(loss)

    inner_axes = tuple(a for a in mesh.axis_names if a != "pod")

    @partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(), P(), P(), P("pod")),
        out_specs=(P(), P(), P(), P()), check_vma=False,
    )
    def step(params, opt_state, ef, batch):
        l, g = grad_fn(params, batch)  # per-pod mean gradient
        g = ef_apply(g, ef)
        synced, new_ef = [], []
        flat_g, treedef = jax.tree.flatten(g)
        for leaf in flat_g:
            red, err = compressed_psum(leaf, "pod", mean=True)
            synced.append(red.astype(leaf.dtype))
            new_ef.append(err)
        grads = treedef.unflatten(synced)
        ef_out = treedef.unflatten(new_ef)
        lr = warmup_cosine(
            opt_state["step"], peak_lr=rc.learning_rate,
            warmup_steps=rc.warmup_steps,
        )
        params, opt_state, gnorm = adamw_update(
            grads, opt_state, params, lr=lr, cfg=opt_cfg
        )
        metrics = {"loss": jax.lax.pmean(l, "pod"), "grad_norm": gnorm, "lr": lr}
        return params, opt_state, ef_out, metrics

    def init_ef(params):
        return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)

    return step, init_ef
