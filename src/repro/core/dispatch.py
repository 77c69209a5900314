"""Public MTE GEMM entry point — the framework's "instruction set".

``mte_gemm`` is the single GEMM surface the whole framework (models,
convolutions, MoE experts, attention projections, the serving engine)
calls into.  It plays the role the MTE ISA plays in the paper: callers
state *what* they want (operand shapes, dtypes, epilogue) and the
dispatch layer *grants* an execution plan and routes to a backend:

- ``backend="pallas"``      — kernel-backed execution (compiled Mosaic on
                              a TPU, the Pallas interpreter elsewhere;
                              ``interpret=`` overrides).
- ``backend="xla"``         — plain jnp.dot + fused-by-XLA epilogue.  Used
                              inside pjit'd training/serving graphs and for
                              the multi-pod dry-run (Mosaic cannot lower on
                              the CPU backend).
- ``backend="reference"``   — the pure-jnp oracle from kernels/ref.py.

**Plan-cache request→grant flow** (the ``tss`` handshake, memoized):
every kernel-backed call builds a
:class:`repro.core.autotune.GemmSignature` from its operands **and its
format policy** (in ``kernels/ops.py`` / ``kernels/autodiff.py``) and
asks the process-global plan cache for an
:class:`~repro.core.autotune.ExecutionPlan`.  The first request for a
signature enumerates candidate plans — MTE block-geometry neighbours
around the analytic ``solve_block_geometry`` point, the transposed-B
layout of Formula 3, split-K with solver-chosen ``n_split`` for
tall/skinny shapes (decode GEMVs: M ≤ 32 or N ≤ 32 with deep K), grouped
batching — scores them with :func:`repro.core.perfmodel.tpu_gemm_time`,
and memoizes the winner; every later request is a cache hit that skips
the solver entirely.  The granted route changes which kernel launches:
the MTE block schedule, split-K, the rigid baseline, or (after measured
refinement) the fused XLA dot.  The XLA/reference backends execute a
single fused dot regardless, so they skip planning entirely — XLA
schedules its own tiling.

**The format dimension** (``format_policy=``): callers may name a
:class:`repro.core.formats.FormatPolicy` — ``"fp32"``, ``"bf16"``,
``"bf16acc"`` (bf16 accumulator fast path) or ``"int8"`` (quantize →
integer-dot → dequantize epilogue, symmetric per-channel scales).  The
policy is the SEW field of the paper's CSR made into an API contract:
it sets the operand cast / quantization *once* here instead of ad-hoc
``astype`` at every call site, becomes part of the GemmSignature (so
each format gets its own searched-and-cached plan: the E8 sublane is
32, Formula 3's widening layout exists only when SEW_i < SEW_o, and
``tpu_gemm_time`` credits the narrower SEW with a higher MXU rate and
fewer HBM bytes), and decides the accumulator dtype every kernel route
carries.  ``format_policy=None`` infers the policy from the operand
dtype, which reproduces the pre-format behaviour exactly.

**Adding a new candidate kernel route**: see the module docstring of
:mod:`repro.core.autotune` — emit the candidate geometry there, name the
route, teach ``autotune.execute_plan`` / ``kernels/ops.py`` /
``kernels/autodiff.py`` to launch it; dispatch needs no changes.

Geometry/ISA statistics are available via ``plan_gemm`` for benchmarks,
without running anything — the analytical path the paper's Table IX and
Fig. 7 reproductions use.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax.numpy as jnp

from repro.core.epilogue import Epilogue
from repro.core.geometry import (
    BlockGeometry, Policy, TPU_V5E, TpuProfile, solve_block_geometry,
)
from repro.core import perfmodel
from repro.core.perfmodel import TpuGemmTiming, tpu_gemm_time
from repro.core.tile_state import SEW

__all__ = ["GemmPlan", "plan_gemm", "mte_gemm"]

_DEFAULT_BACKEND = "xla"


@dataclasses.dataclass(frozen=True)
class GemmPlan:
    """A granted execution plan for one GEMM (the dry 'tss' handshake)."""

    m: int
    n: int
    k: int
    geometry: BlockGeometry
    timing: TpuGemmTiming

    @property
    def efficiency(self) -> float:
        return self.timing.efficiency


def plan_gemm(m: int, n: int, k: int, dtype_in=jnp.float32,
              dtype_out=None, policy: Policy = "mte",
              profile: TpuProfile = TPU_V5E, n_cores: int = 1,
              format_policy=None) -> GemmPlan:
    """Analytic plan + modeled timing (no execution).  A ``format_policy``
    overrides the dtype pair with the policy's operand/accumulator widths
    — the SEW sweep entry point for benchmarks."""
    if format_policy is not None:
        from repro.core.formats import resolve_format
        fmt = resolve_format(format_policy)
        dtype_in, dtype_out = fmt.operand_jnp, fmt.accum_jnp
    dtype_out = dtype_out or dtype_in
    sew_i = SEW.from_dtype(dtype_in)
    sew_o = SEW.from_dtype(dtype_out)
    geom = solve_block_geometry(m, n, k, sew_i, sew_o, profile=profile,
                                policy=policy, n_cores=n_cores)
    timing = tpu_gemm_time(geom, m, n, k, profile=profile)
    return GemmPlan(m=m, n=n, k=k, geometry=geom, timing=timing)


def mte_gemm(a, b, c=None, bias=None, *,
             epilogue: Optional[Epilogue] = None,
             policy: Policy = "mte",
             backend: str = _DEFAULT_BACKEND,
             out_dtype=None,
             format_policy=None,
             interpret: Optional[bool] = None):
    """Compute ``epilogue(a @ b [, c, bias])`` with a plan-cached schedule.

    a: (M, K); b: (K, N); optional c: (M, N) when ``epilogue.beta != 0``;
    optional bias: (N,) or (M,) per ``epilogue.bias_axis``.
    ``format_policy`` (name, FormatPolicy, or None ⇒ inferred from
    ``a.dtype``) sets the operand/accumulator element widths: operands
    are cast (or int8-quantized with per-channel scales) here, the
    accumulator runs at the policy's ``SEW_o``, and the output is cast
    to ``out_dtype`` (defaults to f32 for narrowing/quantized formats,
    input dtype otherwise).
    """
    from repro.core import formats
    epilogue = epilogue or Epilogue()
    fmt = formats.resolve_format(format_policy, a.dtype)
    m, k = a.shape
    k2, n = b.shape
    if k != k2:
        raise ValueError(f"GEMM contraction mismatch: {a.shape} @ {b.shape}")
    if out_dtype is None:
        out_dtype = (jnp.float32
                     if (fmt.quantized or fmt.operand_jnp
                         in (jnp.bfloat16, jnp.int8))
                     else jnp.dtype(a.dtype))

    # Request→grant happens where the grant changes which kernel
    # launches: the pallas path consults the plan cache in
    # kernels/ops.py + kernels/autodiff.py (one plan per (signature,
    # format); repeat calls are cache hits).  The XLA/reference paths
    # execute a single fused dot regardless, so no plan is solved for
    # them — but they honor the same format policy so all three
    # backends agree numerically.
    if backend == "pallas":
        from repro.kernels import ops
        # ops.mte_gemm records into an active repro.graph capture itself.
        return ops.mte_gemm(a, b, c=c, bias=bias, epilogue=epilogue,
                            policy=policy, out_dtype=out_dtype,
                            format_policy=fmt, interpret=interpret)
    from repro.telemetry import gemm_account
    if backend == "reference":
        from repro.kernels import ref
        with gemm_account.suppress():
            out = ref.mte_gemm(a, b, c=c, bias=bias, epilogue=epilogue,
                               out_dtype=out_dtype, format_policy=fmt)
    else:
        # XLA path: one dot at the policy's accumulator width + jnp
        # epilogue; XLA fuses the epilogue into the GEMM consumer on TPU,
        # matching MTE's in-register vector-mode post-ops.
        with gemm_account.suppress():
            acc = formats.xla_gemm(a, b, fmt)
        out = epilogue.apply(acc.astype(jnp.float32)
                             if fmt.quantized else acc, c_in=c, bias=bias)
        out = out.astype(out_dtype)
    from repro.graph import trace as graph_trace
    sink = graph_trace.active()
    if sink is not None:
        sink.record_gemm(a, b, out, c=c, bias=bias, epilogue=epilogue,
                         fmt=fmt.name, policy=policy, out_dtype=out_dtype,
                         backend=backend)
    acct = gemm_account.active()
    if acct is not None:
        # XLA/reference execute one fused dot without consulting the
        # planner, so the account carries no plan grant for them; the
        # analytic perf model still supplies the modeled time so the
        # profiler's calibration join covers planner-bypassing traffic.
        acct.record_gemm(m, n, k, fmt=fmt.name, policy=policy,
                         backend=backend, plan_source="unplanned",
                         modeled_s=perfmodel.analytic_seconds(
                             m, n, k, fmt=fmt.name, policy=policy))
    return out
