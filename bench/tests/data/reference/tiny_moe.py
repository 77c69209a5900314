"""A GraniteMoe block at test size (the granite-3.0 MoE of Hugging Face
``transformers``) as the program's ``granite_moe_1b`` runs it, without
Granite's embedding, residual, attention and logit multipliers:
pre-RMSNorm, rotary embeddings, grouped-query attention without biases,
sparse SwiGLU experts in every layer, tied LM head."""
from bench.reference.decoder import (  # noqa: F401
    CONTROL, Shape, head_weights, layer_weights, seed_key, sequence_logits)

# GraniteMoe's config names one expert's width ``intermediate_size``.
EXPERT_WIDTH = "intermediate_size"


def shape(c: dict) -> Shape:
    n = c["num_hidden_layers"]
    return Shape(
        n_layers=n, d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"],
        head_dim=c.get("head_dim")
        or c["hidden_size"] // c["num_attention_heads"],
        d_ff=c["intermediate_size"], vocab=c["vocab_size"],
        rope_theta=float(c["rope_theta"]), norm="rmsnorm",
        norm_eps=float(c["rms_norm_eps"]), mlp="swiglu", qkv_bias=False,
        o_bias=False, mlp_bias=False, served=c["torch_dtype"], window=None,
        kinds=(("attn", "moe"),) * n, n_experts=c["num_local_experts"],
        top_k=c["num_experts_per_tok"], d_ff_expert=c[EXPERT_WIDTH],
        tied=c["tie_word_embeddings"])
