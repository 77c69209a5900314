"""A hybrid block at test size: each layer's attention global or
sliding-window as the file's ``layer_types`` say, sparse SwiGLU experts
in every layer (Mixtral's routing, arXiv:2401.04088), pre-RMSNorm, rotary
embeddings, grouped-query attention without biases, untied LM head."""
from bench.reference.decoder import (  # noqa: F401
    CONTROL, Shape, head_weights, layer_weights, seed_key, sequence_logits)

MIXERS = {"full_attention": "attn", "sliding_attention": "local"}


def shape(c: dict) -> Shape:
    return Shape(
        n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        d_ff=c["intermediate_size"], vocab=c["vocab_size"],
        rope_theta=float(c["rope_theta"]), norm="rmsnorm",
        norm_eps=float(c["rms_norm_eps"]), mlp="swiglu", qkv_bias=False,
        o_bias=False, mlp_bias=False, served=c["torch_dtype"],
        window=c["sliding_window"],
        kinds=tuple((MIXERS[t], "moe") for t in c["layer_types"]),
        n_experts=c["num_experts"], top_k=c["num_experts_per_tok"],
        d_ff_expert=c["moe_intermediate_size"],
        tied=c["tie_word_embeddings"])
