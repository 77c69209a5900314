"""Operations and bytes that the served work needs, from the model's shapes.

Counted per call of each weight-bearing matrix product that a layer
declares (the reference module's ``shape.layer_work(layer)``): for a
dense decoder layer the q/k/v, output and MLP projections, for an expert
layer the same projections, the router and each expert's SwiGLU; and the
LM head. Operands and results are bf16 (2 bytes), the head's logits
float32. A call's least time on a chip is the larger of its operations
over the peak rate and its bytes over the memory bandwidth; summing that
over the calls gives the time a perfect implementation of those products
would need, which is what ``matmul_roofline`` divides by the device time
of the trace's product operations. The count is of what the algorithm
needs: weights read once per call, no padding rows, so it is the same
whatever route the program takes.

A product every row goes through (``per`` ``"row"``) counts for every
row. An expert's product (``"assignment"``) counts its operations for
each assignment of a row to an expert held here, and its weights once
for each held expert read. Those counts come from the engine's counters
(:data:`COUNTERS`, :func:`routed`) when it reports them. Without them
only what is certain counts, so that no count exceeds what a perfect
implementation needs: with every expert held, ``top_k`` assignments per
row and ``top_k`` experts read per call (a call reads at least the
``top_k`` distinct experts of one row); with a share held here, no
expert work, since how many of a row's experts lie here is not known.

The attention score and value products are left out of that count: the
program's XLA route computes decode attention in elementwise loop fusions
over the gathered cache, which no HLO category marks as a product, so
their time is not in the denominator either (counting their work there
read 104% on a chat trace). Their operations do count in the useful work
of ``step_mfu`` (:func:`attention_flops`), layer by layer, each
attention layer over its own window.
"""
from __future__ import annotations

import dataclasses
import functools

BF16 = 2
# The engine's cumulative counts of expert work, over every expert layer
# and device call: assignments of a real row to an expert held here, and
# held experts that at least one real row was assigned to.
COUNTERS = ("moe_assignments", "moe_experts_read")


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

    def __mul__(self, n: float) -> "Work":
        return Work(self.flops * n, self.bytes * n, self.least_s * n)


@functools.lru_cache(maxsize=None)
def _layers(s):
    return tuple(s.layer_work(i) for i in range(s.n_layers))


def gemms(s, layer: int = 0):
    """(N, K) of each product that every row of ``layer`` goes through."""
    return [(n, k) for per, n, k in _layers(s)[layer]["products"]
            if per == "row"]


def _experts(lw: dict, rows: float):
    """(expert products, assignments, experts read) certain for ``rows``
    rows through one expert layer; no products for another layer."""
    if "top_k" not in lw or not lw["all_held"] or not rows:
        return [], 0, 0
    return ([(n, k) for per, n, k in lw["products"] if per == "assignment"],
            lw["top_k"] * rows, lw["top_k"])


def _expert_work(w: Work, products, a: float, e: float, peak: float,
                 bw: float):
    """``a`` assignments through each of ``products``, whose weights are
    read for ``e`` experts."""
    for n, k in products:
        w.add(2.0 * a * n * k, BF16 * (a * k + e * k * n + a * n), peak, bw)


def _gemm_work(s, rows: float, peak: float, bw: float,
               experts: bool) -> Work:
    w = Work()
    for lw in _layers(s):
        for per, n, k in lw["products"]:
            if per == "row":
                w.add(2.0 * rows * n * k,
                      BF16 * (rows * k + k * n + rows * n), peak, bw)
        if experts:
            _expert_work(w, *_experts(lw, rows), peak, bw)
    return w


def _head_work(s, rows: float, peak: float, bw: float) -> Work:
    w = Work()
    w.add(2.0 * rows * s.d_model * s.vocab,
          BF16 * (rows * s.d_model + s.d_model * s.vocab)
          + 4 * rows * s.vocab, peak, bw)
    return w


def decode_steps(s, n_steps: int, n_tokens: int, peak: float, bw: float,
                 experts: bool = True) -> Work:
    """``n_steps`` batched decode steps that produced ``n_tokens`` tokens,
    counted at the steps' mean row count: the least time is convex in the
    rows, so this never counts more than the steps needed. ``experts``:
    count the expert work that is certain (False where :func:`routed`
    counts it from the engine's counters)."""
    if not n_steps or not n_tokens:
        return Work()
    rows = n_tokens / n_steps
    one = _gemm_work(s, rows, peak, bw, experts) + _head_work(s, rows, peak,
                                                              bw)
    return one * n_steps


def chunk(s, size: int, peak: float, bw: float,
          experts: bool = True) -> Work:
    """One prefill chunk of ``size`` tokens; the head runs on its last
    position only."""
    return _gemm_work(s, size, peak, bw, experts) + _head_work(s, 1, peak,
                                                                bw)


def routed(s, assignments: float, experts_read: float, peak: float,
           bw: float) -> Work:
    """The expert products of ``assignments`` assignments to held experts
    and ``experts_read`` held experts read, summed over the expert layers
    (the engine's :data:`COUNTERS`), as one call per product: a lower
    bound of the calls' own sum."""
    products = {tuple((n, k) for per, n, k in lw["products"]
                      if per == "assignment") for lw in _layers(s)} - {()}
    if len(products) > 1:
        raise ValueError("expert layers of different widths: the counters "
                         "do not say which of them the work went to")
    w = Work()
    for p in products:
        _expert_work(w, p, assignments, experts_read, peak, bw)
    return w


def token_flops(s, experts: bool = True) -> float:
    """Operations of one token through every layer's products; with
    ``experts``, those of its expert work that is certain."""
    total = 0
    for lw in _layers(s):
        total += sum(n * k for per, n, k in lw["products"] if per == "row")
        if experts:
            products, a, _ = _experts(lw, 1)
            total += a * sum(n * k for n, k in products)
    return 2.0 * total


def head_flops(s) -> float:
    return 2.0 * s.d_model * s.vocab


def _attention(s):
    return [lw["attention"] for lw in _layers(s)
            if lw.get("attention") is not None]


def _kv(window, length: int) -> int:
    return min(length, window) if window else length


def attention_flops(s, kv_len: int) -> float:
    """Score and value products of one query over ``kv_len`` positions,
    in every attention layer, each over its own window."""
    return 4.0 * sum(hq * _kv(win, kv_len) for hq, win in _attention(s))


def prompt_attention_flops(s, prompt: int) -> float:
    """Causal attention operations of one unpadded prompt."""
    total = 0
    for hq, win in _attention(s):
        full = _kv(win, prompt)
        total += hq * (full * (full + 1) // 2 + (prompt - full) * full)
    return 4.0 * total
