"""StarCoder2 block (arXiv:2402.19173; ``Starcoder2`` in Hugging Face
``transformers``): pre-LayerNorm with biases, rotary embeddings, grouped-
query attention with biases on q/k/v/o and a sliding window on every
layer, a plain MLP with biases and the tanh form of GELU, untied LM head."""
from bench.reference.decoder import (  # noqa: F401
    CONTROL, Shape, head_weights, layer_weights, seed_key, sequence_logits)


def shape(c: dict) -> Shape:
    return Shape(
        n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"],
        head_dim=c["hidden_size"] // c["num_attention_heads"],
        d_ff=c["intermediate_size"], vocab=c["vocab_size"],
        rope_theta=float(c["rope_theta"]), norm="layernorm",
        norm_eps=float(c["norm_epsilon"]), mlp="gelu_tanh",
        qkv_bias=c["use_bias"], o_bias=c["use_bias"],
        mlp_bias=c["use_bias"], served=c["torch_dtype"],
        window=c["sliding_window"])
