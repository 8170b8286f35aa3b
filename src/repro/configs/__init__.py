"""Architecture registry: ``--arch <id>`` resolution, shape applicability,
and per-(arch x shape) execution defaults (microbatching / remat / optimizer
state dtype) sized so every cell fits 16 GB/chip on the production meshes.
"""
from __future__ import annotations

import dataclasses

from .base import SHAPES, ModelConfig, RunConfig, ShapeConfig, scaled_down  # noqa: F401

from . import (  # noqa: E402
    arctic_480b,
    falcon_mamba_7b,
    gemma3_27b,
    granite_34b,
    internvl2_1b,
    jamba_1_5_large,
    jamba2_mini,
    llama4_maverick_400b,
    mixtral_8x7b,
    phi3_mini_3_8b,
    qwen3_0_6b,
    seamless_m4t_v2,
)

REGISTRY: dict[str, ModelConfig] = {
    m.CONFIG.name: m.CONFIG
    for m in (
        llama4_maverick_400b, arctic_480b, internvl2_1b, granite_34b,
        phi3_mini_3_8b, gemma3_27b, qwen3_0_6b, seamless_m4t_v2,
        jamba_1_5_large, falcon_mamba_7b, mixtral_8x7b, jamba2_mini,
    )
}

# CLI aliases: underscores, short names.
ALIASES = {
    "llama4": "llama4-maverick-400b-a17b",
    "llama4-maverick-400b": "llama4-maverick-400b-a17b",
    "arctic": "arctic-480b",
    "internvl2": "internvl2-1b",
    "granite": "granite-34b",
    "phi3": "phi3-mini-3.8b",
    "phi3-mini": "phi3-mini-3.8b",
    "phi3-mini-3-8b": "phi3-mini-3.8b",  # resolve() maps _ -> - but not .
    "gemma3": "gemma3-27b",
    "qwen3": "qwen3-0.6b",
    "qwen3-0-6b": "qwen3-0.6b",
    "seamless": "seamless-m4t-large-v2",
    "seamless-m4t-v2": "seamless-m4t-large-v2",
    "jamba": "jamba-1.5-large-398b",
    "jamba-1.5-large": "jamba-1.5-large-398b",
    "falcon-mamba": "falcon-mamba-7b",
    "mixtral": "mixtral-8x7b",
}


def resolve(arch: str) -> ModelConfig:
    key = arch.replace("_", "-").lower()
    key = ALIASES.get(key, key)
    if key not in REGISTRY:
        raise KeyError(f"unknown arch {arch!r}; known: {sorted(REGISTRY)}")
    return REGISTRY[key]


# Archs with sub-quadratic attention structure run the long_500k cell
# (SSM / hybrid / mostly-sliding-window / mostly-chunked); pure
# full-attention archs skip it per the task spec (noted in DESIGN.md).
LONG_CONTEXT_ARCHS = {
    "llama4-maverick-400b-a17b",  # 3/4 layers chunked-local 8192
    "gemma3-27b",  # 5/6 layers sliding-window 1024
    "jamba-1.5-large-398b",  # 7/8 layers Mamba
    "jamba2-mini",  # 7/8 layers Mamba
    "falcon-mamba-7b",  # pure SSM
}


def supported_shapes(name: str) -> list[str]:
    shapes = ["train_4k", "prefill_32k", "decode_32k"]
    if name in LONG_CONTEXT_ARCHS:
        shapes.append("long_500k")
    return shapes


def all_cells() -> list[tuple[str, str]]:
    """Every runnable (arch, shape) cell (40 assigned minus documented skips)."""
    return [(a, s) for a in REGISTRY for s in supported_shapes(a)]


# ---------------------------------------------------------------------------
# Execution defaults per (arch x shape): memory-driven, see DESIGN.md §5.
# ---------------------------------------------------------------------------

_BIG = {"llama4-maverick-400b-a17b", "arctic-480b", "jamba-1.5-large-398b"}
_MEDIUM = {"granite-34b", "gemma3-27b"}


def run_config(name: str, shape: str, **overrides) -> RunConfig:
    rc = RunConfig()
    kw: dict = {}
    if shape == "train_4k":
        if name in _BIG:
            kw.update(microbatches=8, remat="full", opt_state_dtype="bfloat16")
        elif name in _MEDIUM:
            kw.update(microbatches=4, remat="full")
        elif name in ("phi3-mini-3.8b", "falcon-mamba-7b"):
            kw.update(microbatches=2, remat="full")
        else:
            kw.update(microbatches=1, remat="full")
    else:
        kw.update(remat="none")
    if shape == "long_500k":
        kw.update(seq_shard=True)
    kw.update(overrides)
    return dataclasses.replace(rc, **kw)
