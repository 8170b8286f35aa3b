"""jamba2-mini [hybrid] — 32L d_model=4096 32H (GQA kv=8) d_ff=14336
vocab=65536, MoE 16e top-2, Mamba:attn 7:1.
[hf:ai21labs/AI21-Jamba2-Mini config.json, model_type "jamba"]

The Jamba 1.5 Mini family at its published sizes.  JambaConfig's rule
orders the layer types: layer i is attention iff i % 8 == 4
(``attn_layer_period`` 8, ``attn_layer_offset`` 4) and MoE iff
i % 2 == 1 (``expert_layer_period`` 2, ``expert_layer_offset`` 1), so one
period of 8 sublayers holds 7 Mamba-1 mixers (d_state 16, d_conv 4,
expand 2, dt_rank 256), one GQA attention mixer without a sliding window,
4 dense SwiGLU MLPs and 4 16-expert top-2 SwiGLU MoE FFNs of width
``intermediate_size``.  Jamba's attention has no positional encoding; the
rotary step this repo's attention applies is elementwise and folds into
its producer in a traced graph, so the evaluator's figures do not see it.
"""
from .base import ModelConfig

CONFIG = ModelConfig(
    name="jamba2-mini",
    family="hybrid",
    n_layers=32,
    d_model=4096,
    n_heads=32,
    n_kv_heads=8,
    head_dim=128,
    d_ff=14_336,
    vocab_size=65_536,
    layer_pattern=(
        "mamba", "mamba", "mamba", "mamba", "attn", "mamba", "mamba", "mamba",
    ),
    n_experts=16,
    top_k=2,
    moe_every=2,
    moe_offset=1,
    ffn_act="swiglu",
    ssm_state=16,
    ssm_conv=4,
    ssm_expand=2,
    ssm_dt_rank=256,
    rmsnorm_eps=1e-6,
    tie_embeddings=False,
    max_seq_len=262_144,
)
