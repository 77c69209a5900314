"""granite-4.0-h's block at test size: the benchmark's
``granite4_h_small`` reference, with 8 experts of which the first 4 are
held, two periods of (Mamba-2 x 5, NoPE attention, Mamba-2 x 4)."""
from bench.reference.granite4_h_small import (  # noqa: F401
    CONTROL, EXPERT_WIDTH, Shape, head_weights, layer_weights, seed_key,
    sequence_logits, shape)
