"""granite-4.0-h-small [hybrid moe]: 40L d_model=4096, 36 Mamba-2 + 4
attention layers (no positions), every layer followed by 72 routed
experts of 768 (top 10) and a shared expert of 1536; vocab=100352, tied.
[hf:ibm-granite/granite-4.0-h-small config.json, model_type
granitemoehybrid]

``layer_types`` puts attention at layers 5, 15, 25 and 35: the period
(5 Mamba, 1 attention, 4 Mamba) four times, which ``block_types`` states
once.  Scalars: embeddings x12, attention softmax scale 1/128, both
residual branches x0.22, logits / 16.  ``CONFIG``'s expert layer is the
capacity-factor ``moe_ffn`` over all 72 experts; ``SMOKE`` and the
benchmark's one-chip cut take the dropless layer (``moe_dropless``) over
a share of them, as one chip of 8-way expert parallelism holds (0, 9)."""

from repro.models import ModelConfig

PERIOD = ("ssd",) * 5 + ("attn",) + ("ssd",) * 4

CONFIG = ModelConfig(
    name="granite-4.0-h-small",
    family="hybrid",
    n_layers=40,
    d_model=4096,
    n_heads=32,
    n_kv_heads=8,
    head_dim=128,
    vocab=100352,
    block_types=PERIOD,
    pos_kind="none",
    n_experts=72,
    top_k=10,
    moe_d_ff=768,
    shared_expert_d_ff=1536,
    ssm_state=128,
    ssm_head_dim=64,
    ssm_expand=2,
    ssm_conv=4,
    ssm_conv_bias=True,
    ssm_chunk=256,
    ssm_ffn=True,
    embedding_multiplier=12.0,
    attention_multiplier=0.0078125,
    residual_multiplier=0.22,
    logits_scaling=16.0,
    norm_eps=1e-5,
    tie_embeddings=True,
)

SMOKE = CONFIG.replace(
    name="granite-4.0-h-smoke",
    n_layers=10,
    d_model=64,
    n_heads=4,
    n_kv_heads=2,
    head_dim=16,
    vocab=512,
    n_experts=8,
    top_k=2,
    moe_d_ff=32,
    moe_dropless=True,
    expert_share=(2, 2),
    shared_expert_d_ff=48,
    ssm_state=16,
    ssm_head_dim=16,
    ssm_chunk=8,
    attention_multiplier=0.0625,
)
