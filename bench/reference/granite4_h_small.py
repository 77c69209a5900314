"""GraniteMoeHybrid block (granite-4.0-h of Hugging Face ``transformers``),
plain float32.

Each layer is pre-RMSNorm, a mixer, then sparse experts with a shared one:

- the mixer is Mamba-2 or grouped-query attention, as the file's
  ``layer_types`` say. Mamba-2 (arXiv:2405.21060) projects ``z``, ``xBC``
  and ``dt`` (``in_proj``, no bias), runs ``xBC`` through a causal
  depthwise conv with bias and SiLU, and steps the recurrence
  ``h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t``, ``y_t = C_t h_t + D x_t``
  one position at a time (``dt_t = softplus(dt + dt_bias)``, ``A =
  -exp(A_log)``, one group of ``B`` and ``C`` for every head); ``y``
  gated by ``silu(z)`` is RMS-normalised over the whole inner width and
  goes through ``out_proj``. It shares nothing with the program's chunked
  dual form. Attention has no position embedding (``position_embedding_
  type`` ``nope``) and scales its scores by ``attention_multiplier``;
- the experts are routed as ``decoder.py`` routes them (softmax over all
  ``num_local_experts``, top ``num_experts_per_tok``, renormalised, no
  capacity), of which this chip holds the first ``experts_here`` ids; the
  shared SwiGLU expert of ``shared_intermediate_size`` adds its output for
  every row;
- the embedding's output is multiplied by ``embedding_multiplier``, each
  residual branch by ``residual_multiplier``, and the logits are divided by
  ``logits_scaling``. The LM head is the embedding table.

The weights come from the seed as in ``decoder.py``: matrices in the served
width, an expert's from its own id; the Mamba-2 vectors (``A_log``,
``dt_bias``, ``D``, the conv) in float32 from Mamba-2's own initialisation
ranges. The forward runs in blocks of equal size, one layer at a time, at
``Precision.HIGHEST``; ``quant`` (the control) narrows every projection,
expert and shared-expert product as ``decoder.py`` does, and leaves the
router, the recurrence and the LM head at full width.
"""

import dataclasses
import functools
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import decoder as dec
from bench.reference.decoder import (  # noqa: F401
    CONTROL, HIGHEST, seed_key, small_leaf)

# The config names one expert's width ``intermediate_size``.
EXPERT_WIDTH = "intermediate_size"
MIXERS = {"mamba": "ssd", "attention": "attn"}


@dataclasses.dataclass(frozen=True)
class Shape(dec.Shape):
    """``decoder.Shape`` with Mamba-2 layers, a shared expert and the
    multipliers."""
    d_ff_shared: int = 0
    embed_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    attn_scale: float = 0.0
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    d_state: int = 0
    conv_width: int = 0

    def kind(self, layer: int) -> Tuple[str, str]:
        return self.kinds[layer]

    @property
    def d_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def d_in_proj(self) -> int:
        return 2 * self.d_inner + 2 * self.d_state + self.ssm_heads

    def layer_work(self, layer: int) -> dict:
        """As ``decoder.Shape.layer_work``: a Mamba-2 layer's ``in_proj``
        and ``out_proj`` and an attention layer's projections, the router
        and the shared expert per row, the experts per assignment."""
        d, hd = self.d_model, self.head_dim
        out = {}
        if self.kind(layer)[0] == "ssd":
            rows = [(self.d_in_proj, d), (d, self.d_inner)]
        else:
            rows = [(self.n_heads * hd, d), (self.n_kv_heads * hd, d),
                    (self.n_kv_heads * hd, d), (d, self.n_heads * hd)]
            out["attention"] = (self.n_heads * hd, None)
        fs, f = self.d_ff_shared, self.d_ff_expert
        rows += [(self.n_experts, d), (fs, d), (fs, d), (d, fs)]
        out["products"] = (
            [("row", n, k) for n, k in rows]
            + [("assignment", n, k) for n, k in ((f, d), (f, d), (d, f))])
        out.update(top_k=self.top_k, all_held=self.held == self.n_experts)
        return out


def shape(c: dict) -> Shape:
    held = c.get("experts_here")
    program_held = c.get("program", {}).get("moe", {}).get("held")
    if held != program_held:
        raise ValueError(f"experts_here {held} and the program's moe.held "
                         f"{program_held} must agree")
    if c["position_embedding_type"] != "nope" or c["mamba_n_groups"] != 1 \
            or not c["mamba_conv_bias"] or c["mamba_proj_bias"] \
            or c["attention_bias"]:
        raise NotImplementedError("NoPE attention without biases and one "
                                  "group of Mamba-2 with a conv bias only")
    d = c["hidden_size"]
    if c["mamba_n_heads"] * c["mamba_d_head"] != c["mamba_expand"] * d:
        raise ValueError("mamba_n_heads x mamba_d_head != mamba_expand x "
                         "hidden_size")
    return Shape(
        n_layers=c["num_hidden_layers"], d_model=d,
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"],
        head_dim=d // c["num_attention_heads"],
        d_ff=c["intermediate_size"], vocab=c["vocab_size"],
        rope_theta=float(c["rope_theta"]), norm="rmsnorm",
        norm_eps=float(c["rms_norm_eps"]), mlp="swiglu", qkv_bias=False,
        o_bias=False, mlp_bias=False, window=None, served=c["torch_dtype"],
        kinds=tuple((MIXERS[t], "moe") for t in c["layer_types"]),
        n_experts=c["num_local_experts"], top_k=c["num_experts_per_tok"],
        d_ff_expert=c[EXPERT_WIDTH], experts_here=held,
        tied=c["tie_word_embeddings"],
        d_ff_shared=c["shared_intermediate_size"],
        embed_multiplier=float(c["embedding_multiplier"]),
        residual_multiplier=float(c["residual_multiplier"]),
        logits_scaling=float(c["logits_scaling"]),
        attn_scale=float(c["attention_multiplier"]),
        ssm_heads=c["mamba_n_heads"], ssm_head_dim=c["mamba_d_head"],
        d_state=c["mamba_d_state"], conv_width=c["mamba_d_conv"])


# -- weights ------------------------------------------------------------------


def _uniform(key, layer, name: str, shape_):
    code = sum((i + 1) * ord(ch) for i, ch in enumerate(name))
    k = jax.random.fold_in(jax.random.fold_in(key, layer + 1), code)
    return jax.random.uniform(k, shape_, jnp.float32)


def layer_weights(shape: Shape, key, small_key, layer
                  ) -> Dict[str, jnp.ndarray]:
    """Weights of ``layer`` (an int, or a sequence of layers of one kind,
    stacked), named as the program names its leaves: as in ``decoder.py``,
    with the residual branches' output projections at
    :func:`branch_gain`."""
    if isinstance(layer, (int, np.integer)):
        return _layer_weights(shape, key, small_key, layer,
                              shape.kind(int(layer)))
    layers = [int(i) for i in layer]
    kinds = {shape.kind(i) for i in layers}
    if len(kinds) != 1:
        raise ValueError(f"layers {layers} are of kinds {kinds}")
    (kind,) = kinds
    return jax.vmap(lambda i: _layer_weights(
        shape, key, small_key, i, kind))(jnp.asarray(layers, jnp.int32))


def branch_gain(s: Shape) -> float:
    """The gain of each residual branch's output projection (``out_proj``,
    ``o``, the experts' and the shared expert's ``down``) over variance
    1 / fan-in.

    The table is tied and enters the stack times ``embedding_multiplier``,
    so with branches of unit size the final state is mostly the input's own
    row, whose logit then stands ``embedding_multiplier x logits_scaling``
    (192) spreads above every other: each position would repeat its input
    token with no near tie, and no precision could be told from another.
    At this gain the ``2 x n_layers`` branches, each times
    ``residual_multiplier``, add up to about that size, so the input's own
    logit is about one spread above the rest (the tiny test cell: a median
    margin of 0.22 and near ties at 11% of positions)."""
    return s.embed_multiplier * s.logits_scaling / (
        s.residual_multiplier * (2 * s.n_layers) ** 0.5)


def _layer_weights(shape: Shape, key, small_key, layer, kind):
    s = shape
    d = s.d_model
    gain = branch_gain(s)
    lk = jax.random.split(key, s.n_layers + 2)[layer]
    ks = jax.random.split(lk, 4)
    mk = jax.random.split(ks[0], 4)
    w = {}
    if kind[0] == "ssd":
        w["in_proj"] = dec._dense(mk[0], d, s.d_in_proj)
        w["out_proj"] = dec._dense(mk[1], s.d_inner, d,
                                   gain * s.d_inner ** -0.5)
    else:
        hd = s.head_dim
        w["q"] = dec._dense(mk[0], d, s.n_heads * hd)
        w["k"] = dec._dense(mk[1], d, s.n_kv_heads * hd)
        w["v"] = dec._dense(mk[2], d, s.n_kv_heads * hd)
        w["o"] = dec._dense(mk[3], s.n_heads * hd, d,
                            gain * (s.n_heads * hd) ** -0.5)
    rk, ek, sk = jax.random.split(ks[1], 3)
    f = s.d_ff_expert

    def expert(e):
        gk, uk, dk = jax.random.split(jax.random.fold_in(ek, e), 3)
        return (dec._dense(gk, d, f), dec._dense(uk, d, f),
                dec._dense(dk, f, d, gain * f ** -0.5))
    w["router"] = dec._dense(rk, d, s.n_experts)
    w["gate"], w["up"], w["down"] = jax.vmap(expert)(jnp.arange(s.held))
    sg = jax.random.split(sk, 3)
    w["shared_gate"] = dec._dense(sg[0], d, s.d_ff_shared)
    w["shared_up"] = dec._dense(sg[1], d, s.d_ff_shared)
    w["shared_down"] = dec._dense(sg[2], s.d_ff_shared, d,
                                  gain * s.d_ff_shared ** -0.5)
    w = {n: m.astype(s.served) for n, m in w.items()}
    if kind[0] == "ssd":
        # Mamba-2's initialisation ranges: A in [1, 16], dt in [1e-3, 0.1]
        # through softplus, a conv of fan-in conv_width.
        conv_dim = s.d_inner + 2 * s.d_state
        h = s.ssm_heads
        w["conv_w"] = jax.random.normal(ks[2], (s.conv_width, conv_dim),
                                        jnp.float32) * s.conv_width ** -0.5
        w["conv_b"] = small_leaf(small_key, layer, "conv_b", (conv_dim,),
                                 "bias")
        w["A_log"] = jnp.log(1.0 + 15.0 * _uniform(small_key, layer, "A_log",
                                                   (h,)))
        dt = jnp.exp(np.log(1e-3) + np.log(100.0) * _uniform(
            small_key, layer, "dt", (h,)))
        w["dt_bias"] = dt + jnp.log(-jnp.expm1(-dt))   # softplus^-1(dt)
        w["D"] = small_leaf(small_key, layer, "D", (h,), "scale")
        w["norm_scale"] = small_leaf(small_key, layer, "norm_scale",
                                     (s.d_inner,), "scale")
    for n in ("norm1", "norm2"):
        w[n] = small_leaf(small_key, layer, n, (d,), "scale")
    return w


def head_weights(shape: Shape, key, small_key):
    """The tied embedding table (in the served width) and the final norm.

    The logits are divided by ``logits_scaling``, so the table is drawn at
    standard deviation ``logits_scaling / sqrt(d_model)`` (0.25 at d 4096):
    the logits of a unit-scale final state then have unit spread, which is
    what ``check.NEAR_TIE`` takes for granted. The input's own row would
    outweigh them but for :func:`branch_gain`."""
    ek = jax.random.split(key, shape.n_layers + 2)[-2]
    d = shape.d_model
    table = jax.random.normal(ek, (shape.vocab, d), jnp.float32) \
        * shape.logits_scaling * d ** -0.5
    table = table.astype(shape.served)
    return {"table": table, "head": table.T,
            "final": small_leaf(small_key, -1, "final_norm", (d,), "scale")}


# -- the forward ---------------------------------------------------------------


def _mamba(s: Shape, w, h, quant):
    """Mamba-2 on h (B, T, D), one position at a time."""
    b, t, _ = h.shape
    di, n, nh, hp = s.d_inner, s.d_state, s.ssm_heads, s.ssm_head_dim
    zxbcdt = dec._mm(h, w["in_proj"], quant)
    z = zxbcdt[..., :di]
    xbc = zxbcdt[..., di:2 * di + 2 * n]
    dt = zxbcdt[..., 2 * di + 2 * n:]
    wc = s.conv_width
    padded = jnp.pad(xbc, ((0, 0), (wc - 1, 0), (0, 0)))
    conv = sum(padded[:, i:i + t] * w["conv_w"][i] for i in range(wc))
    xbc = jax.nn.silu(conv + w["conv_b"])
    xh = xbc[..., :di].reshape(b, t, nh, hp)
    bm, cm = xbc[..., di:di + n], xbc[..., di + n:]
    dt = jax.nn.softplus(dt + w["dt_bias"])                  # (B, T, H)
    a = -jnp.exp(w["A_log"])

    def step(state, inp):
        x_t, dt_t, b_t, c_t = inp
        state = (jnp.exp(dt_t * a)[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None] * b_t[:, None, None])
        return state, jnp.sum(state * c_t[:, None, None], axis=-1)

    _, y = jax.lax.scan(step, jnp.zeros((b, nh, hp, n), jnp.float32),
                        (xh.swapaxes(0, 1), dt.swapaxes(0, 1),
                         bm.swapaxes(0, 1), cm.swapaxes(0, 1)))
    y = (y.swapaxes(0, 1) + w["D"][:, None] * xh).reshape(b, t, di)
    g = y * jax.nn.silu(z)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True) + s.norm_eps) \
        * w["norm_scale"]
    return dec._mm(g, w["out_proj"], quant)


def _attn(s: Shape, w, h, quant, q_block: int):
    b, t, _ = h.shape
    hd = s.head_dim
    q = dec._mm(h, w["q"], quant).reshape(b, t, s.n_heads, hd)
    k = dec._mm(h, w["k"], quant).reshape(b, t, s.n_kv_heads, hd)
    v = dec._mm(h, w["v"], quant).reshape(b, t, s.n_kv_heads, hd)
    # decoder._attention scales by hd ** -0.5; the queries carry the rest.
    q = q * (s.attn_scale * hd ** 0.5)
    return dec._mm(dec._attention(s, q, k, v, q_block, None), w["o"], quant)


def layer_forward(shape: Shape, w, x, kind: Tuple[str, str],
                  quant: Optional[str] = None, q_block: int = 512):
    """One layer of ``kind`` on x (B, T, D) float32."""
    s = shape
    h = dec._norm(s, x, w["norm1"])
    mix = _mamba(s, w, h, quant) if kind[0] == "ssd" \
        else _attn(s, w, h, quant, q_block)
    x = x + s.residual_multiplier * mix
    h = dec._norm(s, x, w["norm2"])
    f = jax.nn.silu(dec._mm(h, w["shared_gate"], quant)) \
        * dec._mm(h, w["shared_up"], quant)
    ffn = dec._moe(s, w, h, quant) + dec._mm(f, w["shared_down"], quant)
    return x + s.residual_multiplier * ffn


def head_forward(shape: Shape, w, x):
    return dec.head_forward(shape, w, x) / shape.logits_scaling


@functools.lru_cache(maxsize=None)
def _programs(shape: Shape, quant: Optional[str]):
    return dict(
        layer=jax.jit(lambda k, sk, i, kind: dec._fp32(_layer_weights(
            shape, k, sk, i, kind)), static_argnums=3),
        head_weights=jax.jit(lambda k, sk: dec._fp32(head_weights(
            shape, k, sk))),
        embed=jax.jit(lambda table, toks: jnp.take(table, toks, axis=0)
                      * shape.embed_multiplier),
        step=jax.jit(lambda w, x, kind: layer_forward(shape, w, x, kind,
                                                      quant),
                     static_argnums=2),
        head=jax.jit(lambda w, x, pos: head_forward(shape, w,
                                                    x[pos][None])[0]))


def sequence_logits(shape: Shape, seed: int, sequences: Sequence[np.ndarray],
                    positions: Sequence[np.ndarray], *,
                    quant: Optional[str] = None,
                    reduce: Optional[Callable] = None,
                    length: Optional[int] = None, rows: Optional[int] = None,
                    n_pos: Optional[int] = None,
                    block_bytes: float = 1.5e9) -> List[np.ndarray]:
    """As ``decoder.sequence_logits``, with the sample cut into blocks of
    equal size: as many sequences to a block as keep the widest
    intermediate (``in_proj``'s output) under ``block_bytes``, rounded
    down to a divisor of the padded sample, so no block is padding."""
    key = seed_key(seed)
    small_key = jax.random.fold_in(key, 0x5EED)
    prog = _programs(shape, quant)
    t = length or max(len(x) for x in sequences)
    p = n_pos or max(len(x) for x in positions)
    n = max(rows or 0, len(sequences))
    widest = max(shape.d_in_proj, shape.n_heads * min(t, 512))
    most = max(1, int(block_bytes // (t * widest * 4)))
    per_block = max(i for i in range(1, min(n, most) + 1) if n % i == 0)
    toks = np.zeros((n, t), np.int32)
    for i, x in enumerate(sequences):
        toks[i, :len(x)] = x
    hw = prog["head_weights"](key, small_key)
    table = hw.pop("table")
    xs = [prog["embed"](table, jnp.asarray(toks[i:i + per_block]))
          for i in range(0, n, per_block)]
    del table
    for layer in range(shape.n_layers):
        kind = shape.kind(layer)
        w = prog["layer"](key, small_key, layer, kind)
        xs = [prog["step"](w, x, kind) for x in xs]
        del w
    out = []
    for i, want in enumerate(positions):
        want = np.asarray(want, np.int32)
        pos = jnp.asarray(np.pad(want, (0, p - len(want)), mode="edge"))
        logits = prog["head"](hw, xs[i // per_block][i % per_block], pos)
        if reduce is None:
            out.append(np.asarray(logits)[:len(want)])
        else:
            out.append(np.asarray(reduce(logits, i))[..., :len(want)])
    return out
