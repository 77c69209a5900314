"""Shared neural-net layers.  Every projection routes through the MTE
dispatch layer so the paper's technique is a first-class feature of the
whole framework (``cfg.gemm_backend``: "xla" inside pjit graphs / dry-run,
"pallas" for kernel-backed execution).

Precision is owned by the model's :class:`repro.core.formats.FormatPolicy`
(``cfg.format_policy``, falling back to ``cfg.compute_dtype``): ``dense``
and the MoE expert FFN hand the policy to the GEMM layer instead of
``astype``-ing operands at every call site, so q/k/v/o projections, MLPs
and experts all switch between fp32 / bf16 / bf16acc / int8-with-scales
by flipping one config field.  The LM head (``unembed``) deliberately
stays un-quantized (≥ bf16 logits).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.epilogue import ACTIVATIONS, Epilogue
from repro.core import formats as formats_lib

__all__ = ["dense", "rmsnorm", "layernorm", "norm", "init_norm", "rope",
           "init_dense", "mlp", "init_mlp", "init_embedding", "embed",
           "unembed", "ffn_param_specs", "model_format", "use_graph"]


def _cdt(cfg):
    return jnp.dtype(cfg.compute_dtype)


def model_format(cfg) -> formats_lib.FormatPolicy:
    """The model's data-format policy: ``cfg.format_policy`` if set,
    otherwise inferred from ``cfg.compute_dtype`` (which reproduces the
    historical per-call-site ``astype(compute_dtype)`` behaviour)."""
    return formats_lib.resolve_format(
        getattr(cfg, "format_policy", None), _cdt(cfg))


def use_graph(cfg) -> bool:
    """True when layer pipelines should execute as compiled
    ``repro.graph`` programs.  The graph path targets the kernel-backed
    backend (its scheduling decisions are plan-cache grants); the XLA
    backend keeps eager jnp dispatch, whose fusion XLA already owns."""
    return (bool(getattr(cfg, "use_graph", False))
            and cfg.gemm_backend == "pallas")


def init_dense(key, d_in: int, d_out: int, *, bias: bool = False,
               dtype=jnp.float32, scale: Optional[float] = None):
    scale = scale if scale is not None else d_in ** -0.5
    w = jax.random.normal(key, (d_in, d_out), dtype) * scale
    p = {"w": w}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def dense(x, p, cfg, *, activation: str = "none"):
    """``act(x @ w + b)`` via the MTE dispatch layer.

    x: (..., d_in).  The Pallas path fuses bias+activation in-kernel (the
    paper's vector-mode epilogue); the XLA path expresses the same epilogue
    as jnp ops for GSPMD graphs, where XLA performs the fusion.  Both
    consume the model's format policy — the operand cast / int8 quantize
    happens inside the GEMM layer, not here.
    """
    cdt = _cdt(cfg)
    fmt = model_format(cfg)
    b = p.get("b")
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    if cfg.gemm_backend == "pallas":
        from repro.kernels import ops
        epi = Epilogue(has_bias=b is not None, activation=activation)
        y = ops.mte_gemm(x2, p["w"], bias=(b.astype(jnp.float32)
                                           if b is not None else None),
                         epilogue=epi, policy=cfg.gemm_policy,
                         out_dtype=cdt, format_policy=fmt)
        return y.reshape(*lead, -1)
    y = formats_lib.xla_gemm(x2, p["w"], fmt).astype(jnp.float32)
    if b is not None:
        y = y + b.astype(jnp.float32)
    y = ACTIVATIONS[activation](y)
    return y.astype(cdt).reshape(*lead, -1)


# -- norms -------------------------------------------------------------------


def init_norm(d: int, kind: str, dtype=jnp.float32):
    p = {"scale": jnp.ones((d,), dtype)}
    if kind == "layernorm":
        p["bias"] = jnp.zeros((d,), dtype)
    return p


def rmsnorm(x, p, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps) * p["scale"].astype(jnp.float32)
    return y.astype(x.dtype)


def layernorm(x, p, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


def norm(x, p, kind: str, eps: Optional[float] = None):
    """``kind`` norm of ``x``; ``eps`` None takes the norm's own default."""
    kw = {} if eps is None else {"eps": eps}
    if kind == "layernorm":
        return layernorm(x, p, **kw)
    return rmsnorm(x, p, **kw)


# -- rotary embeddings ---------------------------------------------------------


def rope(x, positions, theta: float):
    """Apply rotary embeddings.  x: (B, S, H, hd); positions: (B, S) or (S,)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    if positions.ndim == 1:
        positions = positions[None, :]
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B, S, half)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# -- feed-forward -------------------------------------------------------------


def init_mlp(key, cfg):
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    dt = jnp.dtype(cfg.param_dtype)
    if cfg.mlp_type in ("swiglu", "geglu"):
        return {
            "gate": init_dense(ks[0], d, f, bias=cfg.mlp_bias, dtype=dt),
            "up": init_dense(ks[1], d, f, bias=cfg.mlp_bias, dtype=dt),
            "down": init_dense(ks[2], f, d, bias=cfg.mlp_bias, dtype=dt,
                               scale=f ** -0.5),
        }
    return {
        "up": init_dense(ks[0], d, f, bias=cfg.mlp_bias, dtype=dt),
        "down": init_dense(ks[1], f, d, bias=cfg.mlp_bias, dtype=dt,
                           scale=f ** -0.5),
    }


def mlp(x, p, cfg):
    if use_graph(cfg):
        return _mlp_compiled(x, p, cfg)
    if cfg.mlp_type == "swiglu":
        g = dense(x, p["gate"], cfg, activation="silu")
        u = dense(x, p["up"], cfg)
        return dense(g * u, p["down"], cfg)
    if cfg.mlp_type == "geglu":
        g = dense(x, p["gate"], cfg, activation="gelu")
        u = dense(x, p["up"], cfg)
        return dense(g * u, p["down"], cfg)
    h = dense(x, p["up"], cfg, activation="gelu")
    return dense(h, p["down"], cfg)


def _mlp_compiled(x, p, cfg):
    """The MLP block as ONE compiled ``repro.graph`` program.

    Same math as the eager path (each projection = a GemmNode carrying
    the dense epilogue), but fused/scheduled at program level: the
    gate+up siblings of a gated MLP share the input and become one
    grouped launch when the perf model says grouping pays, so the block
    issues fewer kernel dispatches / plan-cache signatures than eager.
    Compiled programs are memoized per (shape, format, type) — repeat
    calls skip graph construction entirely.
    """
    from repro.graph import schedule as graph_schedule
    from repro.graph.trace import GraphBuilder

    cdt = _cdt(cfg)
    fmt = model_format(cfg)
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    m, d = x2.shape
    gated = cfg.mlp_type in ("swiglu", "geglu")
    act = "silu" if cfg.mlp_type == "swiglu" else "gelu"
    names = ("gate", "up", "down") if gated else ("up", "down")
    biased = tuple(n for n in names if "b" in p[n])

    def build():
        b = GraphBuilder()
        xv = b.input((m, d), x2.dtype, "x")
        wv = {n: b.input(p[n]["w"].shape, p[n]["w"].dtype, f"w_{n}")
              for n in names}
        bv = {n: b.input((p[n]["w"].shape[1],), "float32", f"b_{n}")
              for n in biased}

        def proj(src, n, activation="none"):
            return b.gemm(src, wv[n], bias=bv.get(n),
                          epilogue=Epilogue(has_bias=n in biased,
                                            activation=activation),
                          fmt=fmt.name, out_dtype=cdt,
                          policy=cfg.gemm_policy, name=n)

        if gated:
            h = b.mul(proj(xv, "gate", act), proj(xv, "up"))
        else:
            h = proj(xv, "up", "gelu")
        b.output(proj(h, "down"))
        return b.build()

    key = ("mlp", cfg.mlp_type, m, d, cfg.d_ff, fmt.name, str(cdt),
           cfg.gemm_policy, biased, str(x2.dtype),
           str(p[names[0]]["w"].dtype))
    prog = graph_schedule.compile_cached(key, build)
    args = [x2] + [p[n]["w"] for n in names] \
        + [p[n]["b"].astype(jnp.float32) for n in biased]
    return prog(*args).reshape(*lead, -1)


def ffn_param_specs(cfg):
    """Names of the mlp weight matrices (for sharding policy lookups)."""
    if cfg.mlp_type in ("swiglu", "geglu"):
        return ("gate", "up", "down")
    return ("up", "down")


# -- embeddings ---------------------------------------------------------------


def init_embedding(key, cfg):
    dt = jnp.dtype(cfg.param_dtype)
    p = {"table": jax.random.normal(key, (cfg.vocab, cfg.d_model), dt) * 0.02}
    if not cfg.tied_embeddings:
        p["head"] = jax.random.normal(
            jax.random.fold_in(key, 1), (cfg.d_model, cfg.vocab), dt
        ) * cfg.d_model ** -0.5
    return p


def embed(tokens, p, cfg):
    x = jnp.take(p["table"], tokens, axis=0)
    if cfg.embed_multiplier != 1.0:
        x = x.astype(jnp.float32) * cfg.embed_multiplier
    x = x.astype(_cdt(cfg))
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    return x


def unembed(x, p, cfg):
    """LM head → f32 logits (optionally final-softcapped, gemma2).

    The head is never quantized (standard quantized-serving practice):
    under a quantized format policy the operands stay at the compute
    dtype; under float policies the policy's operand width applies.
    """
    fmt = model_format(cfg)
    odt = _cdt(cfg) if fmt.quantized else fmt.operand_jnp
    if cfg.tied_embeddings:
        logits = jnp.einsum("...d,vd->...v", x.astype(odt),
                            p["table"].astype(odt),
                            preferred_element_type=jnp.float32)
    else:
        logits = jnp.einsum("...d,dv->...v", x.astype(odt),
                            p["head"].astype(odt),
                            preferred_element_type=jnp.float32)
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    if cfg.final_softcap is not None:
        logits = cfg.final_softcap * jnp.tanh(logits / cfg.final_softcap)
    return logits
