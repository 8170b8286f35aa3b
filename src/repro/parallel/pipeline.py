"""GPipe-style pipeline parallelism via shard_map + collective_permute.

An optional third way to split the model: ``stages`` consecutive layer
groups live on disjoint device rows, microbatches stream through with
``jax.lax.ppermute`` hand-offs.  The schedule is the classic GPipe fill /
steady / drain loop expressed as one ``lax.scan`` over (microbatches +
stages - 1) ticks: at every tick each stage runs its layer group on the
activation it received last tick, then permutes it to the next stage.

Bubble fraction = (stages-1)/(ticks) — reported by ``bubble_fraction`` —
and the cross-stage traffic is ticks x (mb_tokens x d_model) bytes on the
``stage`` axis, which the dry-run counts as collective-permute bytes.

At the production mesh DPxTP already covers 512 chips for the assigned
models, so PP is exercised at small scale (tests/test_pipeline.py) and
available as a config knob rather than default-on.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def bubble_fraction(n_micro: int, stages: int) -> float:
    ticks = n_micro + stages - 1
    return (stages - 1) / ticks


def pipeline_apply(
    stage_fn,  # (stage_params, x) -> x    (one stage's layer group)
    stacked_params,  # pytree, leaves (stages, ...)  sharded on "stage"
    x_micro,  # (n_micro, mb, ...) microbatched input
    *,
    mesh,
    axis: str = "stage",
):
    """Run the GPipe schedule inside shard_map over the ``stage`` axis.

    Returns (n_micro, mb, ...) outputs (valid after the drain phase).
    """
    stages = mesh.shape[axis]
    n_micro = x_micro.shape[0]
    ticks = n_micro + stages - 1

    @partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(axis), P()),  # params split by stage; data replicated
        out_specs=P(), check_vma=False,
    )
    def run(params, xm):
        stage = jax.lax.axis_index(axis)
        params = jax.tree.map(lambda p: p[0], params)  # my stage's params
        mb_shape = xm.shape[1:]

        def tick(carry, t):
            inflight, outputs = carry
            # Stage 0 ingests microbatch t (if any remain); others take the
            # activation handed over from the previous stage last tick.
            mb_idx = jnp.clip(t, 0, n_micro - 1)
            fresh = xm[mb_idx]
            x_in = jnp.where(stage == 0, fresh, inflight)
            y = stage_fn(params, x_in)
            # Hand off to the next stage (ring; last stage's output wraps to
            # 0 where it is ignored as input but harvested below).
            perm = [(i, (i + 1) % stages) for i in range(stages)]
            handed = jax.lax.ppermute(y, axis, perm)
            # Last stage emits microbatch (t - stages + 1) at tick t.
            out_idx = t - (stages - 1)
            emit = jnp.logical_and(out_idx >= 0, stage == stages - 1)
            outputs = jax.lax.cond(
                emit,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, jnp.clip(out_idx, 0, n_micro - 1), 0
                ),
                lambda o: o,
                outputs,
            )
            return (handed, outputs), None

        inflight0 = jnp.zeros(mb_shape, xm.dtype)
        outputs0 = jnp.zeros((n_micro,) + mb_shape, xm.dtype)
        (_, outputs), _ = jax.lax.scan(
            tick, (inflight0, outputs0), jnp.arange(ticks)
        )
        # Only the last stage holds real outputs; broadcast them.
        outputs = jax.lax.psum(
            jnp.where(stage == stages - 1, outputs, jnp.zeros_like(outputs)),
            axis,
        )
        return outputs

    return run(stacked_params, x_micro)
