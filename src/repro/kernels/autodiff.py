"""Custom VJPs so training differentiates *through* the Pallas kernels.

The backward of a GEMM is two more GEMMs — so the MTE kernels are their
own backward engine:

    out = epilogue(A @ B [, C, bias])
    dacc  = vjp of the (pure-jnp) epilogue at the recomputed accumulator
    dA    = mte_gemm(dacc, Bᵀ)        (kernel)
    dB    = mte_gemm(Aᵀ, dacc)        (kernel)
    dC, dbias from the epilogue vjp

The accumulator is *recomputed* in the backward (flash-style — nothing
saved but the operands), matching the remat philosophy of the training
stack.  The epilogue derivative is obtained with ``jax.vjp`` over
``Epilogue.apply`` — exact for every activation/softcap combination, no
hand-written derivatives to get wrong.

Data formats (:mod:`repro.core.formats`): the forward runs the format's
arithmetic — bf16 / bf16acc operand casts, or int8 quantize →
integer-dot → dequantize — while the backward always runs on the
**full-precision residuals** (the original operands as the caller held
them).  For the quantized formats this is the straight-through
estimator: ``jax.grad`` through an int8 projection equals the fp32
gradient exactly, because round/clip are treated as identity.

flash_attention's backward recomputes through the XLA chunked-attention
formulation (numerically the same math); a dedicated Pallas backward
kernel is the natural next optimization on real hardware.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.epilogue import Epilogue

__all__ = ["mte_gemm_ad", "grouped_gemm_ad", "flash_attention_ad"]


def _plan(m, n, k, dt_in, dt_out, policy, epilogue=None, group=1, fmt=None,
          geometry=None):
    """Fetch (or solve+memoize) the execution plan from the global cache.

    A non-None ``geometry`` pins the plan to that block geometry instead
    (the program-level scheduling override of :mod:`repro.graph.schedule`)
    — no cache lookup or insertion happens for pinned plans.
    """
    from repro.core import autotune
    if geometry is not None:
        return autotune.plan_with_geometry(
            m, n, k, dt_in, dt_out, epilogue=epilogue, policy=policy,
            group=group, fmt=fmt, geometry=geometry)
    return autotune.get_plan(m, n, k, dt_in, dt_out, epilogue=epilogue,
                             policy=policy, backend="pallas", group=group,
                             fmt=fmt)


def _run_plan(plan, a, b, c, bias, interpret):
    """Launch the planned route — one launcher for fwd and bwd GEMMs.

    Delegates to :func:`repro.core.autotune.execute_plan` so every route
    (mte block schedule, split-K, post-measurement XLA fallback) has a
    single launch implementation; epilogue/out_dtype come from the
    plan's signature, which the callers built from the same values.
    """
    from repro.core.autotune import execute_plan
    return execute_plan(plan, a, b, c, bias, interpret=interpret)


def _raw_gemm(a, b, policy, interpret, out_dtype=jnp.float32):
    """Plain A@B through the planned MTE route (no epilogue).  Backward
    GEMMs go through the same plan cache as forward ones, so e.g. the
    dgrad of a decode projection gets its own split-K plan."""
    m, k = a.shape
    n = b.shape[1]
    plan = _plan(m, n, k, a.dtype, out_dtype, policy)
    return _run_plan(plan, a, b, None, None, interpret)


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(4, 5, 6, 7, 8, 9, 10, 11))
def mte_gemm_ad(a, b, c, bias, epilogue: Epilogue, policy: str,
                out_dtype, interpret: bool, has_c: bool, has_bias: bool,
                fmt: str = "fp32", geometry=None):
    """Differentiable fused GEMM routed through the autotune plan cache.
    c/bias are zero-size placeholders when unused (custom_vjp needs a
    static pytree structure).  ``fmt`` names the FormatPolicy the forward
    executes under (the backward ignores it — see module docstring).
    ``geometry`` pins the forward to a program-scheduled block geometry
    (repro.graph) instead of the cached per-GEMM grant; the backward
    GEMMs still plan themselves."""
    from repro.core.formats import FORMATS, dequantize, quantize_operands
    fp = FORMATS[fmt]
    m, k = a.shape
    n = b.shape[1]
    if fp.quantized:
        # quantize → integer-dot (plan-cached per format) → dequantize;
        # the caller's epilogue applies at the dequantized f32
        # accumulator, outside the kernel.  The inner plan carries the
        # identity epilogue so every outer epilogue shares one plan.
        aq, bq, sa, sb = quantize_operands(a, b, fp)
        plan = _plan(m, n, k, aq.dtype, jnp.int32, policy,
                     epilogue=Epilogue(), fmt=fmt, geometry=geometry)
        acc = _run_plan(plan, aq, bq, None, None, interpret)
        acc = dequantize(acc, sa, sb)
        out = epilogue.apply(acc.astype(jnp.float32),
                             c_in=c if has_c else None,
                             bias=bias if has_bias else None)
        return out.astype(out_dtype)
    ac = a.astype(fp.operand_jnp)
    bc = b.astype(fp.operand_jnp)
    plan = _plan(m, n, k, ac.dtype, out_dtype, policy, epilogue=epilogue,
                 fmt=fmt, geometry=geometry)
    return _run_plan(plan, ac, bc,
                     c if has_c else None,
                     bias if has_bias else None, interpret)


def _gemm_fwd(a, b, c, bias, epilogue, policy, out_dtype, interpret,
              has_c, has_bias, fmt, geometry):
    out = mte_gemm_ad(a, b, c, bias, epilogue, policy, out_dtype,
                      interpret, has_c, has_bias, fmt, geometry)
    return out, (a, b, c, bias)


def _gemm_bwd(epilogue, policy, out_dtype, interpret, has_c, has_bias,
              fmt, geometry, res, g):
    # `fmt` is deliberately unused: the backward runs on the
    # full-precision residuals (straight-through estimator).  Residuals
    # may hold mixed dtypes (bf16 activations x f32 params) since the
    # format policy now owns the operand casts, so the backward GEMMs run
    # in the promoted common dtype.
    a, b, c, bias = res
    ct = jnp.result_type(a.dtype, b.dtype)
    af, bf = a.astype(ct), b.astype(ct)
    # Recompute the accumulator with the kernel (flash-style remat).
    acc = _raw_gemm(af, bf, policy, interpret)

    def epi(acc_, c_, bias_):
        return epilogue.apply(acc_, c_in=c_ if has_c else None,
                              bias=bias_ if has_bias else None
                              ).astype(out_dtype)

    _, epi_vjp = jax.vjp(epi, acc, c, bias)
    dacc, dc, dbias = epi_vjp(g)
    dacc = dacc.astype(ct)
    # The backward GEMMs run through the same MTE kernel.
    da = _raw_gemm(dacc, bf.T, policy, interpret).astype(a.dtype)
    db = _raw_gemm(af.T, dacc, policy, interpret).astype(b.dtype)
    return (da, db,
            dc.astype(c.dtype) if has_c else jnp.zeros_like(c),
            dbias.astype(bias.dtype) if has_bias else jnp.zeros_like(bias))


mte_gemm_ad.defvjp(_gemm_fwd, _gemm_bwd)


# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6))
def grouped_gemm_ad(x, w, epilogue: Epilogue, out_dtype, interpret: bool,
                    fmt: str = "fp32", geometry=None):
    from repro.core.formats import FORMATS, dequantize, quantize_operands
    from repro.kernels.grouped_gemm import grouped_gemm_pallas
    fp = FORMATS[fmt]
    g, cap, k = x.shape
    n = w.shape[2]
    if fp.quantized:
        xq, wq, sx, sw = quantize_operands(x, w, fp)
        geom = geometry if geometry is not None else _plan(
            cap, n, k, xq.dtype, jnp.int32, "mte",
            epilogue=Epilogue(), group=g, fmt=fmt).geometry
        acc = grouped_gemm_pallas(xq, wq, geom=geom,
                                  epilogue=Epilogue(),
                                  out_dtype=jnp.int32,
                                  acc_dtype=jnp.int32, interpret=interpret)
        acc = dequantize(acc, sx, sw)
        out = epilogue.apply(acc.astype(jnp.float32))
        return out.astype(out_dtype)
    xc = x.astype(fp.operand_jnp)
    wc = w.astype(fp.operand_jnp)
    geom = geometry if geometry is not None else _plan(
        cap, n, k, xc.dtype, out_dtype, "mte", epilogue=epilogue,
        group=g, fmt=fmt).geometry
    return grouped_gemm_pallas(xc, wc, geom=geom, epilogue=epilogue,
                               out_dtype=out_dtype,
                               acc_dtype=fp.accum_jnp, interpret=interpret)


def _grouped_fwd(x, w, epilogue, out_dtype, interpret, fmt, geometry):
    return (grouped_gemm_ad(x, w, epilogue, out_dtype, interpret, fmt,
                            geometry), (x, w))


def _grouped_bwd(epilogue, out_dtype, interpret, fmt, geometry, res, g):
    # STE: full-precision backward regardless of the forward format;
    # mixed-dtype residuals run in the promoted common dtype.
    from repro.kernels.grouped_gemm import grouped_gemm_pallas
    x_in, w_in = res
    ct = jnp.result_type(x_in.dtype, w_in.dtype)
    x, w = x_in.astype(ct), w_in.astype(ct)
    gg, cap, k = x.shape
    n = w.shape[2]
    geom = _plan(cap, n, k, x.dtype, jnp.float32, "mte", group=gg).geometry
    acc = grouped_gemm_pallas(x, w, geom=geom, epilogue=Epilogue(),
                              out_dtype=jnp.float32, interpret=interpret)
    _, epi_vjp = jax.vjp(lambda a: epilogue.apply(a).astype(out_dtype), acc)
    (dacc,) = epi_vjp(g)
    dacc = dacc.astype(x.dtype)
    wt = jnp.swapaxes(w, 1, 2)
    geom_dx = _plan(cap, k, n, dacc.dtype, jnp.float32, "mte",
                    group=gg).geometry
    dx = grouped_gemm_pallas(dacc, wt, geom=geom_dx, epilogue=Epilogue(),
                             out_dtype=jnp.float32,
                             interpret=interpret).astype(x_in.dtype)
    xt = jnp.swapaxes(x, 1, 2)
    geom_dw = _plan(k, n, cap, xt.dtype, jnp.float32, "mte",
                    group=gg).geometry
    dw = grouped_gemm_pallas(xt, dacc, geom=geom_dw, epilogue=Epilogue(),
                             out_dtype=jnp.float32,
                             interpret=interpret).astype(w_in.dtype)
    return dx, dw


grouped_gemm_ad.defvjp(_grouped_fwd, _grouped_bwd)


# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention_ad(q, k, v, causal: bool, window: Optional[int],
                       softcap: Optional[float], scale: Optional[float],
                       interpret: bool):
    from repro.kernels.flash_attention import flash_attention_pallas
    return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                  softcap=softcap, scale=scale,
                                  interpret=interpret)


def _flash_fwd(q, k, v, causal, window, softcap, scale, interpret):
    out = flash_attention_ad(q, k, v, causal, window, softcap, scale,
                             interpret)
    return out, (q, k, v)


def _flash_bwd(causal, window, softcap, scale, interpret, res, g):
    from repro.models.attention import _xla_attention
    q, k, v = res
    d = q.shape[-1]
    s = scale if scale is not None else 1.0 / (d ** 0.5)

    def ref(q_, k_, v_):
        # The kernel's (B, H, S, D) operands; _xla_attention takes the
        # (B, S, H, D) order.
        def t(a):
            return a.transpose(0, 2, 1, 3)

        return t(_xla_attention(t(q_), t(k_), t(v_), causal=causal,
                                window=window, softcap=softcap, scale=s))

    _, vjp = jax.vjp(ref, q, k, v)
    return vjp(g)


flash_attention_ad.defvjp(_flash_fwd, _flash_bwd)
