"""AI21 Jamba2-Mini — Mamba-1 / GQA attention hybrid with 16-expert MoE
feed-forwards every other layer (one attention layer in each period of
eight; no positional encoding: the Mamba layers carry position).
[hf:ai21labs/AI21-Jamba2-Mini config.json; arXiv:2403.19887]"""
from repro.configs.base import ModelConfig, ATTN, SSM

CONFIG = ModelConfig(
    name="jamba2-mini",
    family="hybrid_moe",
    n_layers=32,
    d_model=4096,
    n_heads=32,
    n_kv_heads=8,
    head_dim=128,
    d_ff=14336,                # dense SwiGLU width, and each expert's
    vocab_size=65536,
    attn_pattern=(SSM, SSM, SSM, SSM, ATTN, SSM, SSM, SSM),
    use_rope=False,            # max_pos 0: no positions at all
    n_experts=16,
    experts_per_token=2,
    router_renorm=False,       # softmax over 16, top-2, gates as they are
    expert_period=2,
    expert_offset=1,
    ssm_state=16,
    ssm_expand=2,
    ssm_conv=4,
    ssm_dt_rank=256,
    ssm_inner_norms=True,
    ssm_conv_bias=True,
    seq_shard=False,
    source="https://huggingface.co/ai21labs/AI21-Jamba2-Mini/blob/main/"
           "config.json",
)


def reduced() -> ModelConfig:
    """One whole period (8 layers) at CPU widths."""
    return CONFIG.replace(
        name="jamba2-mini-reduced", n_layers=8, d_model=128, n_heads=4,
        n_kv_heads=2, head_dim=32, d_ff=256, vocab_size=256, n_experts=4,
        ssm_state=8, ssm_dt_rank=8, lora_rank=4, adapter_heads=4,
        dtype="float32", scan_chunk=16)
