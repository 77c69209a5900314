"""Plain float32 decoder forward: the benchmark's reference.

Written from the published descriptions of Qwen1.5 (Qwen2 architecture in
Hugging Face ``transformers``) and StarCoder2 (arXiv:2402.19173), in
``jax.numpy`` at float32 with every matrix product at
``Precision.HIGHEST``: no kernels, no cache, no batching of requests. It
imports nothing of the program under test.

Each layer has a kind (:meth:`Shape.kind`): a mixer, global (``attn``) or
sliding-window (``local``) attention, and a feed-forward, a dense MLP
(``mlp``) or sparse experts (``moe``). The experts are routed as
``GraniteMoe`` and Mixtral route them: a softmax over the router's logits
for every expert, the ``top_k`` largest, renormalised over those ``k``
(the same as a softmax over the top ``k`` logits), each chosen expert a
SwiGLU, with no capacity and so no token dropped. A chip that holds a
share of the experts (``experts_here``) holds the first ``experts_here``
ids; the router still spans all of them, and the experts held elsewhere
add nothing here.

The weights are made from the run's seed by :func:`layer_weights` and
:func:`head_weights`, in the width they are served in (bfloat16). The
benchmark hands the program the same weights, made by the same functions
(``bench/harness.py``); the reference makes its own copy, one layer at a
time, and holds it in float32.
The forward runs one layer at a time over a few sequences at a time, so it
fits on one chip next to nothing else once the program's state is freed.

``quant`` computes every projection and MLP product in the precision below
the served one (:data:`CONTROL`): for bfloat16, int8 operands
(activations per row, weights per output column, symmetric, as a W8A8
server would); for float32, bfloat16 operands with float32 accumulation.
That is the lower-precision control that the comparison must reject. The
LM head stays at the served width under it, as quantized servers keep it.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
_NEG = -1e30


@dataclasses.dataclass(frozen=True)
class Shape:
    """The sizes and equations of one decoder, read from its config file."""
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_theta: float
    norm: str               # "rmsnorm" | "layernorm"
    norm_eps: float
    mlp: str                # "swiglu" | "gelu_tanh"
    qkv_bias: bool
    o_bias: bool
    mlp_bias: bool
    window: Optional[int]   # the sliding window of ``local`` layers
    served: str = "bfloat16"  # the width the weights are served in
    # (mixer, ffn) of each layer; empty: every layer ("local" with a
    # window, else "attn", and "mlp").
    kinds: Tuple[Tuple[str, str], ...] = ()
    n_experts: int = 0      # the router's outputs
    top_k: int = 0          # experts per token
    d_ff_expert: int = 0
    experts_here: Optional[int] = None  # the first ids held; None: all
    tied: bool = False      # the LM head is the embedding table

    def kind(self, layer: int) -> Tuple[str, str]:
        mixer, ffn = self.kinds[layer] if self.kinds \
            else ("local" if self.window else "attn", "mlp")
        if mixer not in ("attn", "local") or ffn not in ("mlp", "moe"):
            raise NotImplementedError(f"no {mixer}/{ffn} layer here")
        return mixer, ffn

    @property
    def held(self) -> int:
        """How many experts this chip holds."""
        return self.n_experts if self.experts_here is None \
            else self.experts_here

    def layer_work(self, layer: int) -> dict:
        """What the benchmark counts of ``layer`` (``bench/work.py``):
        ``products``, the weight-bearing matrix products as ``(per, N,
        K)``, where ``per`` is ``"row"`` for a product every row goes
        through and ``"assignment"`` for an expert's, which each
        assignment of a row to an expert held here goes through;
        ``attention``, ``(query heads x head size, window)`` of an
        attention layer; ``top_k`` and ``all_held`` of an expert layer."""
        mixer, ffn = self.kind(layer)
        hd, d = self.head_dim, self.d_model
        rows = [(self.n_heads * hd, d), (self.n_kv_heads * hd, d),
                (self.n_kv_heads * hd, d), (d, self.n_heads * hd)]
        out = {"attention": (self.n_heads * hd,
                             self.window if mixer == "local" else None)}
        if ffn == "mlp":
            f = self.d_ff
            rows += [(f, d)] * (len(MLP_NAMES[self.mlp]) - 1) + [(d, f)]
            out["products"] = [("row", n, k) for n, k in rows]
            return out
        f = self.d_ff_expert
        out["products"] = (
            [("row", n, k) for n, k in rows + [(self.n_experts, d)]]
            + [("assignment", n, k) for n, k in ((f, d), (f, d), (d, f))])
        out.update(top_k=self.top_k, all_held=self.held == self.n_experts)
        return out


# -- weights ------------------------------------------------------------------


def seed_key(seed: int):
    """A PRNG key that depends on every bit of a non-negative ``seed``."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def small_leaf(key, layer: int, name: str, shape, kind: str):
    """The value the benchmark gives a bias (``kind="bias"``) or a norm
    scale (``kind="scale"``) of ``layer`` (-1: the final norm). The
    program initialises these at 0 and 1, which would leave them untested."""
    code = sum((i + 1) * ord(c) for i, c in enumerate(name))
    k = jax.random.fold_in(jax.random.fold_in(key, layer + 1), code)
    z = jax.random.normal(k, shape, jnp.float32)
    return 0.1 * z if kind == "bias" else 1.0 + 0.1 * z


def _dense(key, d_in, d_out, scale=None):
    scale = d_in ** -0.5 if scale is None else scale
    return jax.random.normal(key, (d_in, d_out), jnp.float32) * scale


def layer_weights(shape: Shape, key, small_key, layer
                  ) -> Dict[str, jnp.ndarray]:
    """Weights of ``layer``, named as the program names its leaves under
    ``mixer``, ``ffn`` (``q``, ``q_b``, ``router``, ``gate``, ...),
    ``norm1`` and ``norm2`` (``norm1``, ``norm1_b``). ``layer`` is an int,
    or a sequence of layers of one kind, whose weights come stacked on a
    leading axis.

    Matrices come from ``split(key, n_layers + 2)[layer]``, normal with
    variance 1 / fan-in, in the served width; an expert's from its own id,
    so that a share of the experts is the first ids of the whole set;
    biases and norm parameters (float32) from :func:`small_leaf`."""
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


def _layer_weights(shape: Shape, key, small_key, layer, kind
                   ) -> Dict[str, jnp.ndarray]:
    s = shape
    ffn = kind[1]
    lk = jax.random.split(key, s.n_layers + 2)[layer]
    ks = jax.random.split(lk, 4)
    qa = jax.random.split(ks[0], 4)
    hd = s.head_dim
    w = {
        "q": _dense(qa[0], s.d_model, s.n_heads * hd),
        "k": _dense(qa[1], s.d_model, s.n_kv_heads * hd),
        "v": _dense(qa[2], s.d_model, s.n_kv_heads * hd),
        "o": _dense(qa[3], s.n_heads * hd, s.d_model, (s.n_heads * hd) ** -0.5),
    }
    if ffn == "moe":
        if s.mlp != "swiglu" or s.mlp_bias:
            raise NotImplementedError("experts are SwiGLUs without biases")
        rk, ek = jax.random.split(ks[1])
        f = s.d_ff_expert

        def expert(e):
            gk, uk, dk = jax.random.split(jax.random.fold_in(ek, e), 3)
            return (_dense(gk, s.d_model, f), _dense(uk, s.d_model, f),
                    _dense(dk, f, s.d_model, f ** -0.5))
        w["router"] = _dense(rk, s.d_model, s.n_experts)
        w["gate"], w["up"], w["down"] = jax.vmap(expert)(
            jnp.arange(s.held))
    else:
        fk = jax.random.split(ks[1], 3)
        if s.mlp == "swiglu":
            w["gate"] = _dense(fk[0], s.d_model, s.d_ff)
            w["up"] = _dense(fk[1], s.d_model, s.d_ff)
            w["down"] = _dense(fk[2], s.d_ff, s.d_model, s.d_ff ** -0.5)
        else:
            w["up"] = _dense(fk[0], s.d_model, s.d_ff)
            w["down"] = _dense(fk[1], s.d_ff, s.d_model, s.d_ff ** -0.5)
    w = {n: m.astype(shape.served) for n, m in w.items()}
    biased = (["q", "k", "v"] if s.qkv_bias else []) \
        + (list(MLP_NAMES[s.mlp]) if s.mlp_bias and ffn == "mlp" else [])
    for n in biased:
        w[n + "_b"] = small_leaf(small_key, layer, n, (w[n].shape[1],), "bias")
    # No program in this repo carries an output-projection bias; the
    # published StarCoder2 block has one, held at zero here.
    if s.o_bias:
        w["o_b"] = jnp.zeros((s.d_model,), jnp.float32)
    for n in ("norm1", "norm2"):
        w[n] = small_leaf(small_key, layer, n, (s.d_model,), "scale")
        if s.norm == "layernorm":
            w[n + "_b"] = small_leaf(small_key, layer, n + "_bias",
                                     (s.d_model,), "bias")
    return w


MLP_NAMES = {"swiglu": ("gate", "up", "down"), "gelu_tanh": ("up", "down")}


def head_weights(shape: Shape, key, small_key):
    """Embedding table and LM head (in the served width) and final norm.

    The table's entries have variance 1, as large as what each layer adds
    to the residual stream, so that a token's identity outlives the
    layers' constant parts (biases, the mean of GELU) and the next token
    depends on the input: with a table at 0.02, a 16-layer StarCoder2
    stack put nearly every position on the same few tokens, and a check
    of such outputs could not tell a cache left unwritten from a sound
    one.

    A tied table is also the head, and is drawn at the head's variance,
    1 / d_model: at variance 1 its logits would spread by sqrt(d_model),
    and the comparison would meet no near ties. The first layer's norm
    brings its rows to unit scale all the same."""
    served = shape.served
    ek = jax.random.split(key, shape.n_layers + 2)[-2]
    d, v = shape.d_model, shape.vocab
    table = jax.random.normal(ek, (v, d), jnp.float32)
    if shape.tied:
        table = table * d ** -0.5
    w = {
        "table": table.astype(served),
        "head": (jax.random.normal(jax.random.fold_in(ek, 1), (d, v),
                                   jnp.float32) * d ** -0.5).astype(served),
        "final": small_leaf(small_key, -1, "final_norm", (d,), "scale"),
    }
    if shape.norm == "layernorm":
        w["final_b"] = small_leaf(small_key, -1, "final_norm_bias", (d,),
                                  "bias")
    if shape.tied:
        w["head"] = w["table"].T
    return w


def _fp32(w):
    return {n: x.astype(jnp.float32) for n, x in w.items()}


# -- the forward ---------------------------------------------------------------


def _quant_rows(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0.0, 1.0, s)
    return jnp.clip(jnp.round(x / s), -127, 127), s


# The control's precision for each served width: the nearest below it.
CONTROL = {"bfloat16": "int8", "float32": "bf16"}


def _mm(x, w, quant: Optional[str]):
    """x (..., K) @ w (K, N) in float32, or with int8 or bf16 operands."""
    if quant == "bf16":
        return jnp.matmul(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    if quant == "int8":
        # Integers up to 127 are exact in bfloat16: exact products, float32
        # sums.
        xq, sx = _quant_rows(x, -1)
        wq, sw = _quant_rows(w, 0)
        return jnp.matmul(xq.astype(jnp.bfloat16), wq.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32) * sx * sw
    return jnp.matmul(x, w, precision=HIGHEST)


def _norm(shape: Shape, x, scale, bias=None):
    if shape.norm == "rmsnorm":
        var = jnp.mean(x * x, axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(var + shape.norm_eps) * scale
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + shape.norm_eps) * scale + bias


def _rope(x, theta: float):
    """Rotary embedding over the last axis of x (B, T, H, hd), positions
    0..T-1, in the rotate-half layout of the published implementations."""
    t, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    ang = np.arange(t, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(shape: Shape, q, k, v, q_block: int, window: Optional[int]):
    """Causal (optionally windowed) attention, queries in blocks.
    q: (B, T, H, hd); k, v: (B, T, KV, hd) -> (B, T, H * hd)."""
    b, t, h, hd = q.shape
    g = h // shape.n_kv_heads
    kk = jnp.repeat(k, g, axis=2)
    vv = jnp.repeat(v, g, axis=2)
    kpos = jnp.arange(t)
    outs = []
    for start in range(0, t, q_block):
        qb = q[:, start:start + q_block]
        qpos = start + jnp.arange(qb.shape[1])
        s = jnp.einsum("bqhd,bkhd->bhqk", qb, kk,
                       precision=HIGHEST) * hd ** -0.5
        vis = kpos[None, :] <= qpos[:, None]
        if window is not None:
            vis &= kpos[None, :] > qpos[:, None] - window
        s = jnp.where(vis[None, None], s, _NEG)
        p = jax.nn.softmax(s, axis=-1)
        outs.append(jnp.einsum("bhqk,bkhd->bqhd", p, vv, precision=HIGHEST))
    return jnp.concatenate(outs, axis=1).reshape(b, t, h * hd)


def _moe(shape: Shape, w, h, quant: Optional[str]):
    """Sparse experts on h (B, T, D): every token through the experts its
    router picks among all ``n_experts``, of those the ones held here,
    weighted by the renormalised top-k probabilities. The router stays at
    full precision under the control, as the LM head does."""
    probs = jax.nn.softmax(jnp.matmul(h, w["router"], precision=HIGHEST), -1)
    vals, idx = jax.lax.top_k(probs, shape.top_k)
    vals = vals / jnp.sum(vals, axis=-1, keepdims=True)
    # (E_held, B, T): each held expert's weight for each token, 0 unpicked.
    weight = jnp.sum(jnp.where(
        idx[None] == jnp.arange(shape.held)[:, None, None, None],
        vals[None], 0.0), axis=-1)

    def expert(acc, e):
        gate, up, down, wt = e
        f = jax.nn.silu(_mm(h, gate, quant)) * _mm(h, up, quant)
        return acc + wt[..., None] * _mm(f, down, quant), None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                          (w["gate"], w["up"], w["down"], weight))
    return out


def layer_forward(shape: Shape, w, x, kind: Tuple[str, str],
                  quant: Optional[str] = None, q_block: int = 512):
    """One pre-norm decoder layer of ``kind`` on x (B, T, D) float32."""
    s = shape
    mixer, ffn = kind
    b, t, _ = x.shape
    h = _norm(s, x, w["norm1"], w.get("norm1_b"))

    def proj(name, inp):
        y = _mm(inp, w[name], quant)
        return y + w[name + "_b"] if name + "_b" in w else y

    q = proj("q", h).reshape(b, t, s.n_heads, s.head_dim)
    k = proj("k", h).reshape(b, t, s.n_kv_heads, s.head_dim)
    v = proj("v", h).reshape(b, t, s.n_kv_heads, s.head_dim)
    q, k = _rope(q, s.rope_theta), _rope(k, s.rope_theta)
    window = s.window if mixer == "local" else None
    x = x + proj("o", _attention(s, q, k, v, q_block, window))
    h = _norm(s, x, w["norm2"], w.get("norm2_b"))
    if ffn == "moe":
        return x + _moe(s, w, h, quant)
    if s.mlp == "swiglu":
        f = jax.nn.silu(proj("gate", h)) * proj("up", h)
    else:
        f = jax.nn.gelu(proj("up", h), approximate=True)
    return x + proj("down", f)


def head_forward(shape: Shape, w, x):
    """Final norm and LM head: x (B, T, D) -> logits (B, T, V) float32."""
    h = _norm(shape, x, w["final"], w.get("final_b"))
    return jnp.matmul(h, w["head"], precision=HIGHEST)


@functools.lru_cache(maxsize=None)
def _programs(shape: Shape, quant: Optional[str]):
    """The reference's jitted pieces for one shape and precision, built
    once per process."""
    return dict(
        layer=jax.jit(lambda k, sk, i, kind: _fp32(_layer_weights(
            shape, k, sk, i, kind)), static_argnums=3),
        head_weights=jax.jit(lambda k, sk: _fp32(head_weights(shape, k, sk))),
        embed=jax.jit(lambda table, toks: jnp.take(table, toks, axis=0)),
        step=jax.jit(lambda w, x, kind: layer_forward(shape, w, x, kind,
                                                      quant),
                     static_argnums=2),
        head=jax.jit(lambda w, x, pos: head_forward(shape, w, x[pos][None])[0]))


def sequence_logits(shape: Shape, seed: int, sequences: Sequence[np.ndarray],
                    positions: Sequence[np.ndarray], *,
                    quant: Optional[str] = None,
                    reduce: Optional[Callable] = None,
                    length: Optional[int] = None, rows: Optional[int] = None,
                    n_pos: Optional[int] = None,
                    block_bytes: float = 1.5e9) -> List[np.ndarray]:
    """Reference logits of each sequence at the given positions.

    ``sequences[i]`` is the token ids the program served, from position 0;
    ``positions[i]`` the positions whose next-token logits are wanted.
    ``reduce(logits (P, V), i) -> array (..., P)`` shrinks each sequence's
    logits on the device before they are fetched (``None``: fetch them
    whole); only the positions asked for are returned. Runs layer by layer
    over blocks of sequences, with as many sequences to a block as keep its
    largest intermediate under ``block_bytes``.

    ``length``, ``rows`` and ``n_pos`` fix the shapes the device sees, so
    that a process compiles each piece once whatever the sample: sequences
    are padded on the right to ``length`` (causal attention never reads
    the padding), the sample with empty sequences to ``rows``, each
    sequence's positions to ``n_pos`` by repeating its last.
    """
    key = seed_key(seed)
    small_key = jax.random.fold_in(key, 0x5EED)
    prog = _programs(shape, quant)
    t = length or max(len(s) for s in sequences)
    p = n_pos or max(len(x) for x in positions)
    n = max(rows or 0, len(sequences))
    widest = max(shape.d_ff, shape.d_ff_expert, shape.n_experts,
                 shape.n_heads * min(t, 512))
    per_block = min(n, max(1, int(block_bytes // (t * widest * 4))))
    n = -(-n // per_block) * per_block
    toks = np.zeros((n, t), np.int32)
    for i, s in enumerate(sequences):
        toks[i, :len(s)] = s
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
