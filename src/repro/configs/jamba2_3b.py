"""AI21-Jamba2-3B — hybrid Mamba-1 / attention LM (Jamba, arXiv:2403.19887;
https://huggingface.co/ai21labs/AI21-Jamba2-3B/blob/main/config.json).

28L d_model=2560 d_ff=8192 vocab=65536, tied head.  Layer i is attention
when i % 14 == 7 (layers 7 and 21): 20 query heads of 128 over one KV head,
no positional encoding.  Every other layer is the Mamba-1 mixer (d_inner
5120, state 16, conv 4, dt rank 160) with weighted RMS norms on dt, B and C.
Every layer is followed by a SwiGLU MLP (``num_experts`` 1: no MoE).
"""

from repro.utils.config import ModelConfig, ParallelConfig

CONFIG = ModelConfig(
    name="jamba2-3b",
    family="hybrid",
    num_layers=28,
    d_model=2560,
    num_heads=20,
    num_kv_heads=1,
    head_dim=128,
    d_ff=8192,
    vocab_size=65536,
    max_seq_len=262144,
    mlp_type="swiglu",
    norm_eps=1e-6,
    rope_theta=0.0,
    tie_embeddings=True,
    attn_type="gqa",
    ssm_state=16,
    ssm_conv=4,
    ssm_expand=2,
    ssm_chunk=256,
    ssm_dt_bc_norm=True,
    attn_layer_period=14,
    attn_layer_offset=7,
    dtype="bfloat16",
)

SMOKE = CONFIG.replace(
    name="jamba2-smoke", num_layers=4, attn_layer_period=4,
    attn_layer_offset=2, d_model=80, num_heads=5, num_kv_heads=1,
    head_dim=16, d_ff=128, ssm_state=4, ssm_chunk=16, vocab_size=256,
    max_seq_len=2048, dtype="float32",
)


def default_parallel(kind: str) -> ParallelConfig:
    if kind == "train":
        return ParallelConfig(fsdp=2, tp=16, remat="dots", microbatch=1,
                              scan_layers=True)
    return ParallelConfig(fsdp=2, tp=16)
