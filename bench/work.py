"""Operations and bytes that the served work needs, from the model's shapes.

Counted per call of each weight-bearing matrix product of a decoder: the
q/k/v, output and MLP projections of every layer and the LM head.
Operands and results are bf16 (2 bytes), the head's logits float32. A
call's least time on a chip is the larger of its operations over the peak
rate and its bytes over the memory bandwidth; summing that over the calls
gives the time a perfect implementation of those products would need,
which is what ``matmul_roofline`` divides by the device time of the
trace's product operations. The count is of what the algorithm needs:
weights read once per call, no padding rows, so it is the same whatever
route the program takes.

The attention score and value products are left out of that count: the
program's XLA route computes decode attention in elementwise loop fusions
over the gathered cache, which no HLO category marks as a product, so
their time is not in the denominator either (counting their work there
read 104% on a chat trace). Their operations do count in the useful work
of ``step_mfu`` (:func:`attention_flops`).
"""
from __future__ import annotations

import dataclasses
from bench.reference.decoder import Shape

BF16 = 2


@dataclasses.dataclass
class Work:
    flops: float = 0.0
    bytes: float = 0.0
    least_s: float = 0.0      # Σ over calls of max(flops/peak, bytes/bw)

    def add(self, flops: float, nbytes: float, peak: float, bw: float):
        self.flops += flops
        self.bytes += nbytes
        self.least_s += max(flops / peak, nbytes / bw)

    def __add__(self, other: "Work") -> "Work":
        return Work(self.flops + other.flops, self.bytes + other.bytes,
                    self.least_s + other.least_s)


def gemms(s: Shape):
    """(N, K) of each projection in one layer."""
    hd = s.head_dim
    out = [(s.n_heads * hd, s.d_model), (s.n_kv_heads * hd, s.d_model),
           (s.n_kv_heads * hd, s.d_model), (s.d_model, s.n_heads * hd)]
    if s.mlp == "swiglu":
        out += [(s.d_ff, s.d_model), (s.d_ff, s.d_model),
                (s.d_model, s.d_ff)]
    else:
        out += [(s.d_ff, s.d_model), (s.d_model, s.d_ff)]
    return out


def layer_params(s: Shape) -> int:
    return sum(n * k for n, k in gemms(s))


def _kv(s: Shape, length: int) -> int:
    return min(length, s.window) if s.window else length


def _gemm_work(s: Shape, rows: int, peak: float, bw: float) -> Work:
    w = Work()
    for _ in range(s.n_layers):
        for n, k in gemms(s):
            w.add(2.0 * rows * n * k, BF16 * (rows * k + k * n + rows * n),
                  peak, bw)
    return w


def _head_work(s: Shape, rows: int, peak: float, bw: float) -> Work:
    w = Work()
    w.add(2.0 * rows * s.d_model * s.vocab,
          BF16 * (rows * s.d_model + s.d_model * s.vocab)
          + 4 * rows * s.vocab, peak, bw)
    return w


def decode_steps(s: Shape, n_steps: int, n_tokens: int, peak: float,
                 bw: float) -> Work:
    """``n_steps`` batched decode steps that produced ``n_tokens`` tokens,
    counted at the steps' mean row count: the least time is convex in the
    rows, so this never counts more than the steps needed."""
    if not n_steps or not n_tokens:
        return Work()
    rows = n_tokens / n_steps
    one = _gemm_work(s, rows, peak, bw) + _head_work(s, rows, peak, bw)
    return Work(one.flops * n_steps, one.bytes * n_steps,
                one.least_s * n_steps)


def chunk(s: Shape, size: int, peak: float, bw: float) -> Work:
    """One prefill chunk of ``size`` tokens; the head runs on its last
    position only."""
    return _gemm_work(s, size, peak, bw) + _head_work(s, 1, peak, bw)


def token_flops(s: Shape) -> float:
    """Operations of one token through every layer's projections."""
    return 2.0 * s.n_layers * layer_params(s)


def head_flops(s: Shape) -> float:
    return 2.0 * s.d_model * s.vocab


def attention_flops(s: Shape, kv_len: int) -> float:
    """Score and value products of one query over ``kv_len`` positions,
    in every layer."""
    return 4.0 * s.n_heads * s.head_dim * s.n_layers * _kv(s, kv_len)


def prompt_attention_flops(s: Shape, prompt: int) -> float:
    """Causal attention operations of one unpadded prompt."""
    full = min(prompt, s.window) if s.window else prompt
    seen = full * (full + 1) // 2 + (prompt - full) * full
    return attention_flops(s, 1) * seen
