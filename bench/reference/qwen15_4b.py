"""Qwen1.5 block (the Qwen2 architecture of Hugging Face ``transformers``):
pre-RMSNorm, rotary embeddings, multi-head attention with q/k/v biases and
no output bias, SwiGLU MLP without biases, untied LM head."""
from bench.reference.decoder import (  # noqa: F401
    CONTROL, Shape, head_weights, layer_weights, seed_key, sequence_logits)


def shape(c: dict) -> Shape:
    return Shape(
        n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"],
        head_dim=c["hidden_size"] // c["num_attention_heads"],
        d_ff=c["intermediate_size"], vocab=c["vocab_size"],
        rope_theta=float(c["rope_theta"]), norm="rmsnorm",
        norm_eps=float(c["rms_norm_eps"]), mlp="swiglu", qkv_bias=True,
        o_bias=False, mlp_bias=False,
        served=c["torch_dtype"],
        window=c["sliding_window"] if c["use_sliding_window"] else None)
