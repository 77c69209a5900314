"""Jit'd public wrappers for the Pallas kernels.

Each wrapper requests an execution plan from the autotune plan cache
(:mod:`repro.core.autotune`) for the incoming shapes/dtypes **and format
policy** — the ``tss`` request→grant handshake, now memoized and
candidate-searched per format — and invokes the granted route's
``pallas_call``: the MTE block-scheduled kernel, the split-K kernel for
shapes whose (M, N) grid underfills the machine, or the rigid baseline.
``format_policy`` (see :mod:`repro.core.formats`) selects the operand /
accumulator element widths: operands are cast (bf16 / bf16acc) or
symmetric-per-channel quantized (int8 → integer dot → dequantize
epilogue) here, once, instead of at every call site.  ``interpret=None``
compiles to Mosaic on a TPU and runs the Pallas interpreter elsewhere, so
the same entry points serve CPU tests and the chip.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.epilogue import Epilogue
from repro.core import formats as formats_lib
from repro.kernels.rigid_gemm import rigid_gemm_pallas

__all__ = ["mte_gemm", "grouped_gemm", "flash_attention",
           "flash_decode", "flash_decode_paged", "on_tpu"]


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _default_interpret(interpret: Optional[bool]) -> bool:
    return (not on_tpu()) if interpret is None else interpret


def _trace_sink():
    """The active repro.graph capture, if any (None in the common case)."""
    from repro.graph import trace
    return trace.active()


def _account():
    """The active repro.telemetry GEMM accountant (None = no accounting,
    or a higher seam recording this launch itself suppressed us)."""
    from repro.telemetry import gemm_account
    return gemm_account.active_unsuppressed()


def mte_gemm(a, b, c=None, bias=None, *, epilogue: Epilogue = Epilogue(),
             policy: str = "mte", out_dtype=jnp.float32,
             format_policy=None, interpret: Optional[bool] = None,
             geometry=None):
    """Geometry-agnostic GEMM through the autotune plan cache.

    ``policy='amx'`` routes to the rigid baseline; tall/skinny shapes
    whose planned geometry carries ``split_k > 1`` route to the split-K
    kernel.  ``format_policy`` sets the data format (fp32 / bf16 /
    bf16acc / int8-with-scales; None infers from ``a.dtype``).
    ``geometry`` (a BlockGeometry) pins the launch to a program-scheduled
    block shape (repro.graph compiled programs) instead of the cached
    per-GEMM grant.
    Differentiable: backward runs as two more plan-cached MTE GEMMs plus
    the epilogue's jnp vjp on the full-precision residuals — the
    straight-through estimator for the quantized formats
    (kernels/autodiff.py)."""
    from repro.kernels.autodiff import mte_gemm_ad
    interpret = _default_interpret(interpret)
    fmt = formats_lib.resolve_format(format_policy, a.dtype)
    if policy == "amx":
        # The rigid baseline cannot adapt its geometry to the format, but
        # it still executes the format's arithmetic contract.
        if fmt.quantized:
            aq, bq, sa, sb = formats_lib.quantize_operands(a, b, fmt)
            acc = rigid_gemm_pallas(aq, bq, epilogue=Epilogue(),
                                    out_dtype=jnp.int32,
                                    interpret=interpret)
            acc = formats_lib.dequantize(acc, sa, sb)
            out = epilogue.apply(acc.astype(jnp.float32), c_in=c, bias=bias)
            out = out.astype(out_dtype)
        else:
            ac = a.astype(fmt.operand_jnp)
            bc = b.astype(fmt.operand_jnp)
            out = rigid_gemm_pallas(ac, bc, c=c, bias=bias,
                                    epilogue=epilogue,
                                    out_dtype=out_dtype, interpret=interpret)
        sink = _trace_sink()
        if sink is not None:
            sink.record_gemm(a, b, out, c=c, bias=bias, epilogue=epilogue,
                             fmt=fmt.name, policy=policy,
                             out_dtype=out_dtype, backend="pallas")
        acct = _account()
        if acct is not None:
            # The rigid AMX path never consults the planner (fixed tile
            # shape); the analytic model still prices it so the
            # profiler's calibration join covers the baseline too.
            from repro.core import perfmodel
            acct.record_gemm(a.shape[0], b.shape[1], a.shape[1],
                             fmt=fmt.name, policy=policy, backend="pallas",
                             plan_source="unplanned",
                             modeled_s=perfmodel.analytic_seconds(
                                 a.shape[0], b.shape[1], a.shape[1],
                                 fmt=fmt.name, policy=policy))
        return out
    m, k = a.shape
    n = b.shape[1]
    has_c, has_bias = c is not None, bias is not None
    c_ = c if has_c else jnp.zeros((m, n), jnp.float32)
    bias_ = bias if has_bias else jnp.zeros((n,), jnp.float32)
    out = mte_gemm_ad(a, b, c_, bias_, epilogue, policy, out_dtype,
                      interpret, has_c, has_bias, fmt.name, geometry)
    sink = _trace_sink()
    if sink is not None:
        sink.record_gemm(a, b, out, c=c, bias=bias, epilogue=epilogue,
                         fmt=fmt.name, policy=policy, out_dtype=out_dtype,
                         backend="pallas")
    acct = _account()
    if acct is not None:
        acct.record_gemm(m, n, k, fmt=fmt.name, policy=policy,
                         backend="pallas")
    return out


def grouped_gemm(x, w, *, epilogue: Epilogue = Epilogue(),
                 out_dtype=jnp.float32, format_policy=None,
                 interpret: Optional[bool] = None, geometry=None):
    """Per-expert GEMM: x (G, C, K) @ w (G, K, N) -> (G, C, N).
    ``format_policy`` as in :func:`mte_gemm` (per-group per-channel
    scales for int8); ``geometry`` pins a program-scheduled block shape.
    Differentiable (kernels/autodiff.py)."""
    from repro.kernels.autodiff import grouped_gemm_ad
    interpret = _default_interpret(interpret)
    fmt = formats_lib.resolve_format(format_policy, x.dtype)
    out = grouped_gemm_ad(x, w, epilogue, out_dtype, interpret, fmt.name,
                          geometry)
    sink = _trace_sink()
    if sink is not None:
        sink.record_grouped(x, w, out, epilogue=epilogue, fmt=fmt.name,
                            out_dtype=out_dtype, backend="pallas")
    acct = _account()
    if acct is not None:
        acct.record_grouped(w.shape[-3], x.shape[-2], w.shape[-1],
                            x.shape[-1], fmt=fmt.name, policy="mte",
                            backend="pallas")
    return out


def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    softcap: Optional[float] = None,
                    scale: Optional[float] = None,
                    interpret: Optional[bool] = None):
    """Blocked attention with MTE-solved q/kv block sizes."""
    interpret = _default_interpret(interpret)
    sq, skv, d = q.shape[2], k.shape[2], q.shape[3]
    from repro.kernels.autodiff import flash_attention_ad
    return flash_attention_ad(q, k, v, causal, window, softcap, scale,
                              interpret)


def flash_decode(q, k, v, kv_positions, q_pos, *, window=None, softcap=None,
                 scale=None, interpret: Optional[bool] = None):
    """Single-token attention over a (ring) KV cache — serving hot path."""
    from repro.kernels.flash_decode import flash_decode_pallas
    interpret = _default_interpret(interpret)
    return flash_decode_pallas(q, k, v, kv_positions, q_pos, window=window,
                               softcap=softcap, scale=scale,
                               interpret=interpret)


def flash_decode_paged(q, k_pages, v_pages, page_table, seq_lens, *,
                       k_scale=None, v_scale=None, window=None,
                       softcap=None, scale=None,
                       interpret: Optional[bool] = None):
    """Single-token attention over a paged KV pool (page-table-indexed;
    optional in-kernel int8 dequantization) — the paged serving hot path."""
    from repro.kernels.flash_decode import flash_decode_paged_pallas
    interpret = _default_interpret(interpret)
    return flash_decode_paged_pallas(q, k_pages, v_pages, page_table,
                                     seq_lens, k_scale, v_scale,
                                     window=window, softcap=softcap,
                                     scale=scale, interpret=interpret)


def rglru_scan(a, b, *, interpret: Optional[bool] = None):
    """RG-LRU linear recurrence h_t = a_t·h_{t-1} + b_t (serving path)."""
    from repro.kernels.rglru_scan import rglru_scan_pallas
    interpret = _default_interpret(interpret)
    return rglru_scan_pallas(a, b, interpret=interpret)
