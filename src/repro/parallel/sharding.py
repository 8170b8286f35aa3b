"""GSPMD sharding rules for every parameter/activation/cache in the system.

Strategy (DP x TP with ZeRO-3-style FSDP, EP for MoE, SP for long decode):

* **TP** (``model`` axis): attention Q/K/V/O head dims, MLP d_ff
  (column/row parallel), MoE expert axis (expert parallelism), Mamba
  d_inner, vocab dim of embedding/LM head.
* **FSDP** (the data axes, ``("data",)`` or ``("pod","data")``): every
  weight *additionally* sharded over its largest remaining axis, so
  parameters + Adam state for the 400-500 B models fit 16 GB/chip HBM
  (ZeRO-3 storage; GSPMD inserts the all-gathers at use sites).
* **Sequence parallelism**: the `long_500k` decode cell has batch 1, so the
  KV-cache *sequence* axis shards over the data axes instead.

Rules are matched on parameter-path key names, which are stable across the
ten architectures because every model is built from the same modules.
"""
from __future__ import annotations

from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


# Logical axis tokens used by in-model sharding hints.
DP = ("pod", "data")  # data-parallel axes (whichever exist in the mesh)
TP = "model"

# Mesh axis name for the evaluator's hardware-config sharding
# (repro.core.flow.run_fleet(devices=...)): the (G, H, C) sweep's H axis is
# embarrassingly parallel, so it shards over a 1-D device mesh.
HW_AXIS = "hardware"


def hardware_mesh(devices=None, *, axis: str = HW_AXIS) -> Mesh:
    """A 1-D mesh over ``devices`` for hardware-config sharding.

    ``devices`` may be ``None`` (every visible device), an int (the first N
    visible devices — errors if fewer exist), or an explicit device
    sequence.  The axis name defaults to :data:`HW_AXIS`, the name
    :func:`repro.core.metrics.sharded_fleet_kernel` shards over.
    """
    if devices is None:
        devices = jax.devices()
    elif isinstance(devices, int):
        avail = jax.devices()
        if devices < 1:
            raise ValueError(f"need >= 1 device, got {devices}")
        if devices > len(avail):
            raise ValueError(
                f"requested {devices} devices but only {len(avail)} visible "
                "(set XLA_FLAGS=--xla_force_host_platform_device_count=N "
                "for host-platform sharding)"
            )
        devices = avail[:devices]
    devices = np.asarray(devices)
    if devices.size < 1:
        raise ValueError("empty device list")
    return Mesh(devices, (axis,))


def mesh_fingerprint(mesh: Mesh) -> tuple:
    """Hashable identity of a mesh: axis names, size, and device ids.

    This is the cache-key component that keeps executables compiled for one
    device layout from being served to another (an 8-device program is not
    a 1-device program even at identical argument shapes)."""
    return (
        ",".join(mesh.axis_names),
        int(mesh.devices.size),
        tuple(str(d) for d in mesh.devices.flat),
    )


def repair_spec(spec, shape, axis_size) -> "P":
    """Make ``spec`` valid for ``shape``: drop axes a dim cannot host
    (indivisible / too small) and greedily re-place them on the largest
    divisible dim.

    The re-placement is semantically meaningful, not just a fallback: e.g.
    a KV-head axis of 8 cannot host 16-way TP, so TP migrates to the
    sequence axis of the KV cache — which is flash-decode-style sequence
    sharding (partial softmax per shard + small cross-shard reduction).
    """
    out: list = [None] * len(shape)
    dropped: list = []
    used: set = set()
    for i, axis in enumerate(spec[: len(shape)]):
        if axis is None:
            continue
        axes = axis if isinstance(axis, tuple) else (axis,)
        keep = []
        size_so_far = 1
        for a in axes:
            s = axis_size(a)
            if s <= 1 or a in used:
                continue
            if shape[i] % (size_so_far * s) == 0:
                keep.append(a)
                used.add(a)
                size_so_far *= s
            else:
                dropped.append(a)
        if keep:
            out[i] = tuple(keep) if len(keep) > 1 else keep[0]
    order = sorted(range(len(shape)), key=lambda i: -shape[i])
    for a in dropped:
        s = axis_size(a)
        if s <= 1 or a in used:
            continue
        used.add(a)
        for i in order:
            cur = out[i]
            cur_axes = () if cur is None else (cur if isinstance(cur, tuple) else (cur,))
            if a in cur_axes:
                continue
            total = s
            for c in cur_axes:
                total *= axis_size(c)
            if shape[i] % total == 0 and shape[i] >= total:
                out[i] = cur_axes + (a,) if cur_axes else a
                break
    return P(*out)


def _ambient_mesh_auto_axes():
    """(mesh, auto axis names) of the ambient abstract mesh; Manual axes
    (inside shard_map) must not be pinned.  Outside any mesh: (None, ())."""
    am = jax.sharding.get_abstract_mesh()
    if am is None or not am.axis_names:
        return None, ()
    return am, tuple(
        n for n, t in zip(am.axis_names, am.axis_types)
        if t == jax.sharding.AxisType.Auto
    )


def hint(x, *spec):
    """``with_sharding_constraint`` against the ambient (abstract) mesh.

    Model code calls ``hint(q, DP, None, TP, None)``; axes absent from the
    current mesh are dropped, indivisible placements are repaired
    (see :func:`repair_spec`), and outside any mesh (single-device tests)
    this is a no-op.  This is how the models pin the shardings GSPMD cannot
    infer through reshapes (e.g. splitting the head axis into KV groups).
    """
    am, names = _ambient_mesh_auto_axes()
    if am is None:
        return x
    names = set(names)
    if not names:  # fully inside shard_map (Manual axes): nothing to pin
        return x

    def clean(a):
        if a is None:
            return None
        if isinstance(a, tuple):
            kept = tuple(ax for ax in a if ax in names)
            return kept if kept else None
        return a if a in names else None

    spec = tuple(clean(a) for a in spec)
    if len(spec) < x.ndim:
        spec = spec + (None,) * (x.ndim - len(spec))
    fixed = repair_spec(spec, x.shape, lambda a: am.shape[a])
    return jax.lax.with_sharding_constraint(x, fixed)


def _path_names(path) -> list[str]:
    out = []
    for e in path:
        if isinstance(e, jax.tree_util.DictKey):
            out.append(str(e.key))
        elif isinstance(e, jax.tree_util.SequenceKey):
            out.append(str(e.idx))
        elif isinstance(e, jax.tree_util.GetAttrKey):
            out.append(str(e.name))
    return out


# (param name, rank-without-stacking) -> spec builder(dp, tp).
# Specs are written for the *unstacked* parameter; a leading None is added
# per stacking axis (scan segments / vmapped layer stacks).
def _param_rules(dp, tp) -> dict[str, Any]:
    return {
        "embed": P(tp, dp),  # (V, d)
        "lm_head": P(dp, tp),  # (d, V)
        "wq": P(dp, tp),
        "wk": P(dp, tp),
        "wv": P(dp, tp),
        "wo": P(tp, dp),
        "w1": P(dp, tp),  # dense mlp (d, ff) — overridden for MoE by path
        "w3": P(dp, tp),
        "w2": P(tp, dp),  # (ff, d)
        "router": P(dp, None),  # (d, E) tiny
        "moe.w1": P(tp, None, dp),  # (E, d, ff): experts on model (EP)
        "moe.w3": P(tp, None, dp),
        "moe.w2": P(tp, dp, None),  # (E, ff, d)
        "in_proj": P(dp, tp),  # mamba (d, 2*di)
        "conv_w": P(None, tp),  # (dc, di)
        "conv_b": P(tp),
        "x_proj": P(tp, None),  # (di, dr+2ds)
        "dt_proj": P(None, tp),  # (dr, di)
        "dt_bias": P(tp),
        "A_log": P(tp, None),  # (di, ds)
        "D": P(tp),
        "out_proj": P(tp, dp),  # (di, d)
        # norms and qk-norm scales: replicated
        "norm1": P(), "norm2": P(), "norm_x": P(), "final_norm": P(),
        "enc_final_norm": P(), "q_norm": P(), "k_norm": P(),
        # vgg
        "w": P(None, None, None, tp), "b": P(tp),
    }


def _spec_for_param(names: list[str], shape: tuple[int, ...], dp, tp) -> P:
    rules = _param_rules(dp, tp)
    leaf = names[-1]
    key = leaf
    if "moe" in names and leaf in ("w1", "w2", "w3"):
        key = f"moe.{leaf}"
    if "dense_residual" in names and leaf in ("w1", "w2", "w3"):
        key = leaf  # arctic's parallel dense MLP: plain MLP rules
    spec = rules.get(key)
    if spec is None:
        return P()
    # Add leading Nones for stacking axes (scan repeats / vmapped stacks).
    extra = len(shape) - len(spec)
    if extra > 0:
        spec = P(*([None] * extra), *spec)
    elif extra < 0:  # param smaller than rule (e.g. tiny test dims) — replicate
        return P()
    return spec


def data_axes(mesh: Mesh) -> tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def param_shardings(mesh: Mesh, abstract_params, *, fsdp: bool = True):
    """Pytree of NamedSharding matching ``abstract_params``."""
    dp = data_axes(mesh)
    dp = dp if (fsdp and dp) else None
    tp = "model" if "model" in mesh.axis_names else None

    def one(path, leaf):
        names = _path_names(path)
        spec = _spec_for_param(names, leaf.shape, dp, tp)
        spec = _validate(spec, leaf.shape, mesh)
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(one, abstract_params)


def _axis_size(mesh: Mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, tuple):
        return int(np.prod([mesh.shape[a] for a in axis]))
    return mesh.shape[axis]


def _validate(spec: P, shape: tuple[int, ...], mesh: Mesh) -> P:
    """Repair the spec for exact divisibility (inputs/outputs to jit must
    divide evenly) — drops what can't fit and re-places it on the largest
    divisible dim; see :func:`repair_spec`."""
    return repair_spec(tuple(spec) + (None,) * (len(shape) - len(spec)),
                       shape, lambda a: mesh.shape[a] if a else 1)


# ---------------------------------------------------------------------------
# Batch / cache / activation shardings
# ---------------------------------------------------------------------------


def batch_shardings(mesh: Mesh, batch_abstract, *, seq_shard: bool = False):
    """tokens/labels: (B, S) on (dp, None); frontend: (B, L, d).

    ``seq_shard``: batch too small to fill dp (long_500k, B=1) — shard the
    sequence axis over dp instead (sequence parallelism).
    """
    dp = data_axes(mesh)

    def one(path, leaf):
        names = _path_names(path)
        if leaf.ndim == 0:
            return NamedSharding(mesh, P())
        if seq_shard and leaf.ndim >= 2:
            spec = P(None, dp, *([None] * (leaf.ndim - 2)))
        else:
            spec = P(dp, *([None] * (leaf.ndim - 1)))
        return NamedSharding(mesh, _validate(spec, leaf.shape, mesh))

    return jax.tree_util.tree_map_with_path(one, batch_abstract)


def cache_shardings(mesh: Mesh, cache_abstract, *, seq_shard: bool = False):
    """KV caches: (L, B, S, KV, hd) -> (None, dp, None, tp, None); with
    ``seq_shard`` the sequence axis takes dp (batch-1 long-context decode).
    Mamba states: (L, B, ..., di, ...) -> di on tp, batch on dp."""
    dp = data_axes(mesh)
    tp = "model" if "model" in mesh.axis_names else None

    def one(path, leaf):
        names = _path_names(path)
        name = names[-1]
        if leaf.ndim == 0 or name in ("len", "primed"):
            return NamedSharding(mesh, P())
        if name in ("k", "v"):  # (L, B, S, KV, hd) or (B, S, KV, hd)
            lead = [None] * (leaf.ndim - 4)
            if seq_shard:
                spec = P(*lead, None, dp, tp, None)
            else:
                spec = P(*lead, dp, None, tp, None)
        elif name == "conv":  # (L, B, dc-1, di)
            spec = P(*([None] * (leaf.ndim - 3)), dp, None, tp)
        elif name == "h":  # (L, B, di, ds)
            spec = P(*([None] * (leaf.ndim - 3)), dp, tp, None)
        else:
            spec = P()
        return NamedSharding(mesh, _validate(spec, leaf.shape, mesh))

    return jax.tree_util.tree_map_with_path(one, cache_abstract)


def opt_state_shardings(mesh: Mesh, opt_abstract, param_shardings_tree):
    """Adam m/v mirror the parameter shardings; step is replicated."""

    def one(path, leaf):
        names = _path_names(path)
        if names and names[0] in ("m", "v"):
            sub = param_shardings_tree
            for n in names[1:]:
                if isinstance(sub, (list, tuple)):
                    sub = sub[int(n)]
                else:
                    sub = sub[n]
            return sub
        return NamedSharding(mesh, P())

    return jax.tree_util.tree_map_with_path(one, opt_abstract)


def replicate(mesh: Mesh, tree):
    return jax.tree.map(lambda _: NamedSharding(mesh, P()), tree)
