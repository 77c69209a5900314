"""granite-4.0-h-small [hybrid moe]: Mamba-2 and NoPE GQA layers, each
with 72 routed SwiGLU experts (top-10) and a shared one.

40L d_model=4096; 36 Mamba-2 layers (128 heads of 64, d_state 128, one
group, conv 4, expand 2, chunk 256) and GQA at layers 5, 15, 25, 35 (32
query and 8 KV heads of 128, no position embedding, scale 1/128);
experts of 768, shared expert of 1536; embedding x12, residual branches
x0.22, logits /16; tied embedding, vocab 100352, RMSNorm eps 1e-5
[hf:ibm-granite/granite-4.0-h-small].  Every expert is held here; a
deployment that divides them sets ``moe.held`` to this device's share.
"""
from repro.configs.base import ArchConfig, MoEConfig, SSMConfig, register

CONFIG = register(ArchConfig(
    name="granite4_h_small",
    n_layers=40, d_model=4096, n_heads=32, n_kv_heads=8, head_dim=128,
    d_ff=768, vocab=100352,
    pattern=(("ssd", "moe"),) * 5 + (("attn", "moe"),)
    + (("ssd", "moe"),) * 4,
    mlp_type="swiglu", norm_type="rmsnorm", norm_eps=1e-5,
    rope=False, attn_scale=0.0078125, tied_embeddings=True,
    embed_multiplier=12.0, residual_multiplier=0.22, logits_scaling=16.0,
    format_policy="bf16",
    moe=MoEConfig(n_experts=72, top_k=10, d_ff_expert=768, held=72,
                  d_ff_shared=1536),
    ssm=SSMConfig(d_state=128, head_dim=64, expand=2, conv_width=4,
                  chunk=256, norm_eps=1e-5),
))
