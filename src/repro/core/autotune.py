"""Autotuned GEMM plan cache — measured, adaptive dispatch for the MTE ISA.

The geometry solver (:mod:`repro.core.geometry`) answers "what block shape
does Formula 2/3 grant for this GEMM?" analytically.  This module turns
that single answer into a *search*: for every distinct GEMM signature

    (M, N, K, dtype_in, dtype_out, fmt, epilogue, policy, backend[, group])

it enumerates candidate execution plans, scores them with the performance
model (:func:`repro.core.perfmodel.tpu_gemm_time`, occupancy-aware), and
memoizes the winner so the solve cost is paid **once per shape**, not once
per call.  ``fmt`` names the :class:`repro.core.formats.FormatPolicy`
(fp32 / bf16 / bf16acc / int8): the *same* (M, N, K) gets an independent
search, score and cache entry per format, because the format changes both
the candidate set (Formula-3 transposed-B exists only for widening
formats; int8's E8 sublane is 32) and the score (narrower SEW ⇒ higher
MXU rate, fewer HBM bytes).  The plan-cache request→grant flow:

1. A caller (``dispatch.mte_gemm``, ``kernels/ops.py``, conv im2col, MoE
   experts, attention projections, the serving engine) builds a
   :class:`GemmSignature` for its operands.
2. ``PlanCache.plan`` returns the memoized :class:`ExecutionPlan` on a hit
   — no solver call, no candidate scoring.
3. On a miss the candidate set is generated:

   - the **analytic** geometry (``solve_block_geometry``, the fixed plan
     the dispatch layer used before this subsystem existed);
   - **MTE block-geometry neighbours**: bm/bn/bk halved and doubled around
     the analytic point (VMEM-feasible points only);
   - the **transposed-B** layout of Formula 3 (and its row-major
     alternative) for mixed-precision signatures;
   - **split-K** plans with solver-chosen ``n_split ∈ {2, 4, 8}`` whenever
     the (M, N) grid underfills the machine — the paper's tall/skinny
     decode shapes (M ≤ 32 or N ≤ 32 with deep K);
   - for grouped signatures (``group > 1``), the same search over the
     per-expert block schedule.

4. The analytic score ranks candidates; with ``measure=True`` the top
   candidates are additionally timed on the current substrate (interpret
   mode on CPU, compiled Mosaic on TPU) and the measured winner is kept.
5. The winning plan is inserted into an in-process LRU and — when a
   persistence path is configured — can be saved to / warm-started from a
   JSON file, so a serving process starts with a hot cache.

**Adding a new candidate kernel route**: give the route a name in
``ExecutionPlan.route`` (derived in :func:`_route_for`), emit candidate
geometries for it in :func:`enumerate_candidates`, teach
:func:`execute_plan` how to launch it, and (for training) route it in
``kernels/autodiff.py``.  The scoring/caching/persistence machinery is
route-agnostic.
"""
from __future__ import annotations

import dataclasses
import json
import time
from collections import OrderedDict
from typing import Dict, List, Optional, Tuple

from repro.core.epilogue import Epilogue
from repro.core.geometry import (
    BlockGeometry, Policy, TPU_V5E, TpuProfile, cdiv, device_profile,
    round_up, solve_block_geometry,
)
from repro.core.perfmodel import tpu_gemm_time
from repro.core.tile_state import SEW

__all__ = [
    "GemmSignature", "ExecutionPlan", "PlanCache", "CacheStats",
    "enumerate_candidates", "execute_plan", "get_plan", "plan_with_geometry",
    "unsupported",
    "plan_cache",
    "reset_cache", "configure", "cache_stats", "save_plans", "load_plans",
    "benchmark_shape", "benchmark_format", "DEFAULT_N_CORES",
]

# Planning horizon for grid occupancy: a v5e host slice exposes 8 cores
# over which sharded/pmapped GEMMs spread; this is what makes split-K and
# finer blockings pay off for shapes whose (M, N) grid alone cannot fill
# the machine.  Override per-cache via PlanCache(n_cores=...) or globally
# via configure(n_cores=...).
DEFAULT_N_CORES = 8

_SPLIT_CANDIDATES = (2, 4, 8)
# v2: GemmSignature grew the `fmt` (FormatPolicy name) field — v1 files
# cannot be keyed correctly and are rejected on load.
_CACHE_VERSION = 2


def _dtype_name(dt) -> str:
    import jax.numpy as jnp
    return jnp.dtype(dt).name


def _substrate() -> str:
    """The execution substrate measurements are valid for."""
    import jax
    return jax.default_backend()


def _note_plan(sig: "GemmSignature", source: str, predicted_s: float) -> None:
    """Tell the active per-GEMM accountant (repro.telemetry) where this
    signature's grant came from.  ``source`` is ``"cache-hit"`` for a
    memoized grant, the plan's own source otherwise — the provenance the
    dispatch-side record joins against.  No-op when no accountant is
    installed (the common case)."""
    from repro.telemetry import gemm_account
    acct = gemm_account.active()
    if acct is not None:
        acct.note_plan(sig, source, predicted_s)


@dataclasses.dataclass(frozen=True)
class GemmSignature:
    """The cache key: everything that changes which plan wins.

    ``group`` > 1 marks a grouped (per-expert) GEMM whose per-group
    operand shapes are (m, k) × (k, n); plain GEMMs use group=1.
    ``fmt`` names the :class:`repro.core.formats.FormatPolicy` the GEMM
    runs under — distinct formats get distinct plans even when the raw
    operand dtypes coincide (bf16 vs bf16acc differ only in accumulator
    width).
    """

    m: int
    n: int
    k: int
    dtype_in: str
    dtype_out: str
    epilogue: Epilogue
    policy: Policy = "mte"
    backend: str = "pallas"
    group: int = 1
    fmt: str = "fp32"

    @classmethod
    def make(cls, m: int, n: int, k: int, dtype_in, dtype_out,
             epilogue: Optional[Epilogue] = None, policy: Policy = "mte",
             backend: str = "pallas", group: int = 1,
             fmt: Optional[str] = None) -> "GemmSignature":
        if fmt is None:
            from repro.core.formats import infer_format
            fmt = infer_format(dtype_in).name
        return cls(m=int(m), n=int(n), k=int(k),
                   dtype_in=_dtype_name(dtype_in),
                   dtype_out=_dtype_name(dtype_out),
                   epilogue=epilogue or Epilogue(), policy=policy,
                   backend=backend, group=int(group), fmt=str(fmt))

    @property
    def format_policy(self):
        from repro.core.formats import FORMATS, infer_format
        import jax.numpy as jnp
        return FORMATS.get(self.fmt) or infer_format(jnp.dtype(self.dtype_in))

    @property
    def sew_i(self) -> SEW:
        import jax.numpy as jnp
        return SEW.from_dtype(jnp.dtype(self.dtype_in))

    @property
    def sew_o(self) -> SEW:
        import jax.numpy as jnp
        return SEW.from_dtype(jnp.dtype(self.dtype_out))


@dataclasses.dataclass(frozen=True)
class ExecutionPlan:
    """A granted plan: kernel route + block geometry + predicted cost."""

    signature: GemmSignature
    geometry: BlockGeometry
    route: str                       # "mte" | "splitk" | "rigid" | "grouped"
    predicted_s: float
    measured_s: Optional[float] = None
    source: str = "analytic"   # "analytic" | "measured" | "warmstart" |
    #                            "program" (pinned by repro.graph.schedule)

    @property
    def n_split(self) -> int:
        return self.geometry.split_k

    def describe(self) -> str:
        g = self.geometry
        tail = f" split_k={g.split_k}" if g.split_k > 1 else ""
        tail += " bT" if g.transposed_b else ""
        return (f"{self.route}[{g.bm}x{g.bn}x{g.bk}{tail}] "
                f"~{self.predicted_s * 1e6:.2f}us ({self.source})")


def _route_for(sig: GemmSignature, geom: BlockGeometry) -> str:
    if sig.policy == "amx":
        return "rigid"
    if sig.group > 1:
        return "grouped"
    if geom.split_k > 1:
        return "splitk"
    return "mte"


def _pow2_span(v: int, lo: int, hi: int) -> List[int]:
    """v/2, v, 2v clamped to [lo, hi], deduplicated, lo-aligned."""
    out = []
    for cand in (v // 2, v, v * 2):
        cand = max(lo, min(hi, round_up(max(cand, 1), lo)))
        if cand not in out:
            out.append(cand)
    return out


def _vmem_ok(geom: BlockGeometry, profile: TpuProfile) -> bool:
    return geom.vmem_bytes() <= int(profile.vmem_bytes
                                    * profile.vmem_budget_frac)


def _split_bk(base_bk: int, k: int, s: int, sub: int) -> int:
    """Largest block-K ≤ base that still yields ≥ s grid slices of K."""
    bk = min(base_bk, max(sub, round_up(cdiv(k, s), sub)))
    return max(sub, bk - bk % sub)


def enumerate_candidates(sig: GemmSignature,
                         profile: TpuProfile = TPU_V5E,
                         n_cores: int = DEFAULT_N_CORES,
                         ) -> List[BlockGeometry]:
    """Candidate block geometries for one signature, analytic base first.

    Non-"mte" policies model rigid ISAs whose whole point is that they
    cannot adapt, so they get exactly their analytic schedule.
    """
    sew_i, sew_o = sig.sew_i, sig.sew_o
    base = solve_block_geometry(sig.m, sig.n, sig.k, sew_i, sew_o,
                                profile=profile, policy=sig.policy)
    if sig.policy != "mte":
        return [base]

    sub = profile.sublane(sew_i)
    lane = profile.lane
    cands: List[BlockGeometry] = [base]

    def add(geom: BlockGeometry):
        if geom not in cands and _vmem_ok(geom, profile):
            cands.append(geom)

    # MTE block-geometry neighbours around the analytic optimum.
    for bm in _pow2_span(base.bm, sub, 512):
        for bn in _pow2_span(base.bn, lane, 512):
            for bk in _pow2_span(base.bk, sub, 2048):
                add(dataclasses.replace(base, bm=bm, bn=bn, bk=bk))

    # Formula 3 layout choice is real only for mixed precision; offer the
    # alternative of whatever the solver picked.
    if sew_i.bits < sew_o.bits:
        add(dataclasses.replace(base, transposed_b=not base.transposed_b))

    # Split-K: only worth enumerating when the (M, N) grid underfills the
    # cores — decode GEMVs, skinny projections.  Grouped signatures are
    # excluded: the grouped kernel has no split-K execution path, and its
    # group grid dimension already provides the parallelism.  Integer
    # GEMMs participate too: the split kernel accumulates partials in the
    # format's accumulator dtype (int32 for int8), so the quantized
    # decode GEMVs the format policy targets get the K-parallel route.
    grid_mn = cdiv(sig.m, base.bm) * cdiv(sig.n, base.bn)
    if sig.group == 1 and grid_mn < n_cores and sig.k > sub:
        for s in _SPLIT_CANDIDATES:
            bk = _split_bk(base.bk, sig.k, s, sub)
            if cdiv(sig.k, bk) < s:
                continue  # K too shallow for s useful slices
            add(dataclasses.replace(base, bk=bk, split_k=s,
                                    transposed_b=False))
    return cands


def unsupported(plan: ExecutionPlan) -> Optional[str]:
    """Why ``plan``'s route cannot execute its signature, or None.

    Decided from the signature before anything launches, so a plan
    search never has to tell a capability mismatch from a kernel that
    fails to compile.
    """
    epi = plan.signature.epilogue
    if plan.route == "mte" and epi.has_bias and epi.bias_axis != "row":
        return "the MTE kernel fuses row bias only"
    return None


def score_geometry(sig: GemmSignature, geom: BlockGeometry,
                   profile: TpuProfile = TPU_V5E,
                   n_cores: int = DEFAULT_N_CORES) -> float:
    """Predicted seconds for one candidate (analytic model).

    Grouped GEMMs model the group grid dimension as parallelism the
    per-group schedule already enjoys: each group's tiles see only
    ``n_cores / group`` cores' worth of un-filled machine.
    """
    group = max(sig.group, 1)
    eff_cores = max(1, n_cores // group) if group > 1 else n_cores
    t = tpu_gemm_time(geom, sig.m, sig.n, sig.k, profile=profile,
                      n_cores=eff_cores)
    return t.seconds * group


# ---------------------------------------------------------------------------
# Plan execution (measurement / benchmarking path — not differentiable;
# training goes through kernels/autodiff.py which consumes the same plans)
# ---------------------------------------------------------------------------


def execute_plan(plan: ExecutionPlan, a, b, c=None, bias=None, *,
                 interpret: Optional[bool] = None):
    """Launch the plan's kernel route on concrete operands.

    For route "mte" with a transposed-B geometry the caller passes row-major
    b; the transpose to the Formula 3 layout happens here (a BlockSpec
    index-map change inside the kernel, a cheap relayout outside).

    The signature's format decides the accumulator dtype every route
    carries (f32 / bf16 / int32) — quantized signatures receive
    already-quantized int8 operands (the quantize/dequantize halves live
    with the caller in ``kernels/ops.py`` / ``kernels/autodiff.py``).
    The rigid route deliberately ignores the narrow-accumulator fast
    path: a rigid ISA cannot adapt its accumulator width.
    """
    from repro.kernels import ops
    from repro.kernels.mte_gemm import mte_gemm_pallas
    from repro.kernels.rigid_gemm import rigid_gemm_pallas
    from repro.kernels.splitk_gemm import mte_gemm_splitk_pallas
    from repro.kernels.grouped_gemm import grouped_gemm_pallas

    if interpret is None:
        interpret = not ops.on_tpu()
    sig = plan.signature
    epi = sig.epilogue
    geom = plan.geometry
    import jax.numpy as jnp
    out_dtype = jnp.dtype(sig.dtype_out)
    acc_dtype = sig.format_policy.accum_jnp

    if plan.route == "xla":
        return _xla_gemm(a, b, c, bias, epilogue=epi, out_dtype=out_dtype,
                         acc_dtype=acc_dtype)
    if plan.route == "grouped":
        return grouped_gemm_pallas(a, b, geom=geom, epilogue=epi,
                                   out_dtype=out_dtype, acc_dtype=acc_dtype,
                                   interpret=interpret)
    if plan.route == "rigid":
        return rigid_gemm_pallas(a, b, c=c, bias=bias, epilogue=epi,
                                 out_dtype=out_dtype, interpret=interpret)
    if plan.route == "splitk":
        return mte_gemm_splitk_pallas(a, b, c=c, bias=bias, geom=geom,
                                      n_split=geom.split_k, epilogue=epi,
                                      out_dtype=out_dtype,
                                      acc_dtype=acc_dtype,
                                      interpret=interpret)
    bm = b.T if geom.transposed_b else b
    return mte_gemm_pallas(a, bm, c=c, bias=bias, geom=geom, epilogue=epi,
                           out_dtype=out_dtype, acc_dtype=acc_dtype,
                           interpret=interpret)


_XLA_GEMM_JIT = None


def _xla_gemm(a, b, c, bias, *, epilogue: Epilogue, out_dtype,
              acc_dtype=None):
    """The fused-dot route XLA schedules itself (jitted once per shape)."""
    import functools
    import jax
    import jax.numpy as jnp

    global _XLA_GEMM_JIT
    if _XLA_GEMM_JIT is None:
        # One module-level jit so repeat calls hit the compile cache
        # instead of retracing through a fresh closure.
        @functools.partial(jax.jit, static_argnames=("epi", "dt", "at"))
        def run(a_, b_, c_, bias_, epi, dt, at):
            acc = jnp.dot(a_, b_, preferred_element_type=at)
            return epi.apply(acc, c_in=c_, bias=bias_).astype(dt)

        _XLA_GEMM_JIT = run
    acc_dtype = jnp.dtype(acc_dtype) if acc_dtype is not None else jnp.float32
    return _XLA_GEMM_JIT(a, b, c, bias, epilogue, jnp.dtype(out_dtype),
                         acc_dtype)


def _operands_for(sig: GemmSignature, seed: int = 0):
    import jax.numpy as jnp
    import numpy as np
    rng = np.random.default_rng(seed)
    dt = np.dtype(sig.dtype_in)

    def draw(shape):
        if np.issubdtype(dt, np.integer):
            return jnp.asarray(rng.integers(-64, 64, shape), jnp.dtype(dt))
        return jnp.asarray(rng.standard_normal(shape).astype(np.float32)
                           ).astype(jnp.dtype(sig.dtype_in))

    if sig.group > 1:
        # The grouped kernel fuses only the elementwise epilogue (no
        # c/bias operands), so none are synthesized for it.
        return (draw((sig.group, sig.m, sig.k)),
                draw((sig.group, sig.k, sig.n)), None, None)
    a = draw((sig.m, sig.k))
    b = draw((sig.k, sig.n))
    c = bias = None
    if sig.epilogue.needs_c_input:
        c = draw((sig.m, sig.n)).astype(jnp.float32)
    if sig.epilogue.has_bias:
        shape = (sig.n,) if sig.epilogue.bias_axis == "row" else (sig.m,)
        bias = draw(shape).astype(jnp.float32)
    return a, b, c, bias


def measure_plan(plan: ExecutionPlan, iters: int = 3,
                 interpret: Optional[bool] = None) -> float:
    """Median wall-clock seconds of one executed call (1 warmup)."""
    a, b, c, bias = _operands_for(plan.signature)
    execute_plan(plan, a, b, c, bias, interpret=interpret
                 ).block_until_ready()
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        execute_plan(plan, a, b, c, bias, interpret=interpret
                     ).block_until_ready()
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


# ---------------------------------------------------------------------------
# The cache
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class CacheStats:
    hits: int = 0
    misses: int = 0
    solver_calls: int = 0
    measured: int = 0
    measure_failed: int = 0   # candidates a signature could not execute

    def as_dict(self) -> Dict[str, int]:
        return dataclasses.asdict(self)


class PlanCache:
    """In-process LRU of GemmSignature → ExecutionPlan with JSON warm-start."""

    def __init__(self, maxsize: int = 4096,
                 profile: Optional[TpuProfile] = None,
                 n_cores: int = DEFAULT_N_CORES,
                 measure_top: int = 4):
        self.maxsize = maxsize
        self._profile = profile
        self.n_cores = n_cores
        self.measure_top = measure_top
        self._plans: "OrderedDict[GemmSignature, ExecutionPlan]" = OrderedDict()
        self.stats = CacheStats()

    @property
    def profile(self) -> TpuProfile:
        """The hardware profile plans are solved for: the one given, or
        else the device's (``geometry.device_profile``), looked up on
        first use so that importing this module touches no device."""
        if self._profile is None:
            self._profile = device_profile()
        return self._profile

    def __len__(self) -> int:
        return len(self._plans)

    def __contains__(self, sig: GemmSignature) -> bool:
        return sig in self._plans

    def clear(self) -> None:
        self._plans.clear()
        self.stats = CacheStats()

    # -- planning -----------------------------------------------------------
    def plan(self, sig: GemmSignature, *, measure: bool = False,
             interpret: Optional[bool] = None) -> ExecutionPlan:
        hit = self._plans.get(sig)
        if hit is not None:
            # measure=True means "ensure this plan is measured-refined":
            # upgrade an analytic hit in place instead of ignoring the
            # request (serving tunes + save_plans after a cold start).
            # Still a hit — the lookup found an entry; solver_calls
            # records the extra solve the refinement performs.
            if measure and hit.measured_s is None:
                self.stats.hits += 1
                plan = self._build(sig, measure=True, interpret=interpret)
                self._insert(sig, plan)
                _note_plan(sig, "cache-hit", plan.predicted_s)
                return plan
            self.stats.hits += 1
            self._plans.move_to_end(sig)
            _note_plan(sig, "cache-hit", hit.predicted_s)
            return hit
        self.stats.misses += 1
        plan = self._build(sig, measure=measure, interpret=interpret)
        self._insert(sig, plan)
        _note_plan(sig, plan.source, plan.predicted_s)
        return plan

    def _build(self, sig: GemmSignature, *, measure: bool,
               interpret: Optional[bool]) -> ExecutionPlan:
        self.stats.solver_calls += 1
        cands = enumerate_candidates(sig, self.profile, self.n_cores)
        scored = sorted(
            ((score_geometry(sig, g, self.profile, self.n_cores), i, g)
             for i, g in enumerate(cands)),
            key=lambda t: (t[0], t[1]))  # stable: analytic base wins ties
        best_s, _, best_g = scored[0]
        plan = ExecutionPlan(signature=sig, geometry=best_g,
                             route=_route_for(sig, best_g),
                             predicted_s=best_s, source="analytic")
        if not measure:
            return plan
        # Refine by on-substrate timing: the top analytic candidates, the
        # analytic base (never slower than the fixed plan, by
        # construction), and — measured-refinement only — the plain
        # fused-XLA route, so a substrate where the explicit kernels lose
        # (e.g. interpret mode on CPU) routes to the dot it runs best.
        measured_set = scored[:max(2, self.measure_top)]
        if not any(i == 0 for _, i, _ in measured_set):
            measured_set.append(next(t for t in scored if t[1] == 0))
        candidates = [ExecutionPlan(signature=sig, geometry=g,
                                    route=_route_for(sig, g), predicted_s=s)
                      for s, _, g in measured_set]
        if sig.policy == "mte" and sig.group == 1:
            # The fused-dot fallback is a 2-D contraction; grouped
            # signatures keep their batched kernel route.
            candidates.append(ExecutionPlan(signature=sig,
                                            geometry=scored[0][2],
                                            route="xla",
                                            predicted_s=best_s))
        timed: List[Tuple[float, ExecutionPlan]] = []
        for cand in candidates:
            if unsupported(cand) is not None:
                # This route cannot execute the signature, so it cannot
                # win.  Whatever a launch raises (a compile or lowering
                # error, a misaligned block) propagates: a silent
                # fallback to another route would hide a broken kernel.
                self.stats.measure_failed += 1
                continue
            self.stats.measured += 1
            timed.append((measure_plan(cand, interpret=interpret), cand))
        if not timed:
            return plan  # nothing executable to measure: analytic grant
        t_best, p_best = min(timed, key=lambda x: x[0])
        return dataclasses.replace(p_best, measured_s=t_best,
                                   source="measured")

    def _insert(self, sig: GemmSignature, plan: ExecutionPlan) -> None:
        self._plans[sig] = plan
        self._plans.move_to_end(sig)
        while len(self._plans) > self.maxsize:
            self._plans.popitem(last=False)

    # -- plan-quality audit hooks (repro.telemetry.profiler) -----------------
    def analytic_candidates(self, sig: GemmSignature) -> List[ExecutionPlan]:
        """The signature's candidate plans in analytic-score order (best
        first) — the same ranking :meth:`_build` starts from.  The
        profiler's plan-regret audit times the granted plan against the
        first entry here that differs from it (the analytic runner-up)."""
        cands = enumerate_candidates(sig, self.profile, self.n_cores)
        scored = sorted(
            ((score_geometry(sig, g, self.profile, self.n_cores), i, g)
             for i, g in enumerate(cands)),
            key=lambda t: (t[0], t[1]))
        return [ExecutionPlan(signature=sig, geometry=g,
                              route=_route_for(sig, g), predicted_s=s)
                for s, _, g in scored]

    def runner_up(self, sig: GemmSignature) -> Optional[ExecutionPlan]:
        """The best analytic candidate that is NOT the granted plan
        (None when the signature is uncached or has a single candidate)."""
        granted = self._plans.get(sig)
        if granted is None:
            return None
        for cand in self.analytic_candidates(sig):
            if (cand.geometry != granted.geometry
                    or cand.route != granted.route):
                return cand
        return None

    def recalibrate(self, sig: GemmSignature, *,
                    interpret: Optional[bool] = None) -> ExecutionPlan:
        """Re-grant ``sig`` from measurement, replacing the cached entry.

        The plan-regret audit (:mod:`repro.telemetry.profiler`) calls
        this when the granted plan measurably loses to its analytic
        runner-up: the full measured-refinement search of :meth:`_build`
        (``measure=True`` — top analytic candidates, the analytic base,
        and the fused-XLA fallback all timed on the current substrate)
        reruns and the measured winner displaces the stale grant.  The
        new grant is re-announced to the accountant so later dispatch
        records join against the refreshed provenance.
        """
        plan = self._build(sig, measure=True, interpret=interpret)
        self._insert(sig, plan)
        _note_plan(sig, plan.source, plan.predicted_s)
        return plan

    # -- persistence ----------------------------------------------------------
    def to_json(self) -> Dict:
        return {
            "version": _CACHE_VERSION,
            "profile": self.profile.name,
            "n_cores": self.n_cores,
            "substrate": _substrate(),
            "plans": [_plan_to_json(p) for p in self._plans.values()],
        }

    def load_json(self, doc: Dict) -> int:
        """Warm-start from a previously saved document; returns #plans.

        Rejects documents tuned for a different substrate: plans carry
        measured routes and occupancy-scored geometries that only hold
        for the (profile, n_cores) they were tuned on.
        """
        if doc.get("version") != _CACHE_VERSION:
            raise ValueError(f"plan-cache version {doc.get('version')!r} "
                             f"!= {_CACHE_VERSION}")
        if doc.get("profile") != self.profile.name:
            raise ValueError(f"plan cache tuned for profile "
                             f"{doc.get('profile')!r}, this cache is "
                             f"{self.profile.name!r}")
        if doc.get("n_cores") != self.n_cores:
            raise ValueError(f"plan cache tuned for n_cores="
                             f"{doc.get('n_cores')!r}, this cache plans "
                             f"for {self.n_cores}")
        if doc.get("substrate") != _substrate():
            # measured_s / measured routes only hold for the substrate
            # that timed them (interpret-mode CPU routes must not steer
            # a real TPU, and vice versa).
            raise ValueError(f"plan cache measured on substrate "
                             f"{doc.get('substrate')!r}, this process "
                             f"runs on {_substrate()!r}")
        n = 0
        for entry in doc.get("plans", []):
            plan = _plan_from_json(entry)
            self._insert(plan.signature, plan)
            n += 1
        return n

    def save(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.to_json(), f, indent=1, sort_keys=True)

    def load(self, path: str) -> int:
        with open(path) as f:
            return self.load_json(json.load(f))


def _plan_to_json(plan: ExecutionPlan) -> Dict:
    sig, g = plan.signature, plan.geometry
    sd = dataclasses.asdict(sig)
    sd["epilogue"] = dataclasses.asdict(sig.epilogue)
    gd = dataclasses.asdict(g)
    gd["sew_i"], gd["sew_o"] = g.sew_i.name, g.sew_o.name
    return {"sig": sd, "geom": gd, "route": plan.route,
            "predicted_s": plan.predicted_s, "measured_s": plan.measured_s}


def _plan_from_json(entry: Dict) -> ExecutionPlan:
    sd = dict(entry["sig"])
    sd["epilogue"] = Epilogue(**sd["epilogue"])
    sig = GemmSignature(**sd)
    gd = dict(entry["geom"])
    gd["sew_i"], gd["sew_o"] = SEW[gd["sew_i"]], SEW[gd["sew_o"]]
    geom = BlockGeometry(**gd)
    return ExecutionPlan(signature=sig, geometry=geom, route=entry["route"],
                         predicted_s=entry["predicted_s"],
                         measured_s=entry.get("measured_s"),
                         source="warmstart")


# ---------------------------------------------------------------------------
# Process-global cache (what dispatch/ops/autodiff consult)
# ---------------------------------------------------------------------------

_GLOBAL = PlanCache()
_GENERATION = 0


def plan_cache() -> PlanCache:
    return _GLOBAL


def cache_generation() -> int:
    """Bumped on every :func:`reset_cache` — consumers that memoize
    derived state (compiled graph programs pin plans granted here) check
    it so a cache reset invalidates them too."""
    return _GENERATION


def reset_cache(maxsize: int = 4096, n_cores: int = DEFAULT_N_CORES,
                profile: Optional[TpuProfile] = None) -> PlanCache:
    """Replace the process-global cache (tests / reconfiguration)."""
    global _GLOBAL, _GENERATION
    _GLOBAL = PlanCache(maxsize=maxsize, profile=profile, n_cores=n_cores)
    _GENERATION += 1
    return _GLOBAL


def configure(*, n_cores: Optional[int] = None,
              maxsize: Optional[int] = None,
              measure_top: Optional[int] = None) -> PlanCache:
    """Adjust global planning knobs in place (keeps cached plans)."""
    if n_cores is not None:
        _GLOBAL.n_cores = n_cores
    if maxsize is not None:
        _GLOBAL.maxsize = maxsize
    if measure_top is not None:
        _GLOBAL.measure_top = measure_top
    return _GLOBAL


def cache_stats() -> CacheStats:
    return _GLOBAL.stats


def get_plan(m: int, n: int, k: int, dtype_in, dtype_out=None, *,
             epilogue: Optional[Epilogue] = None, policy: Policy = "mte",
             backend: str = "pallas", group: int = 1,
             fmt: Optional[str] = None,
             measure: bool = False,
             interpret: Optional[bool] = None) -> ExecutionPlan:
    """The one-call planning entry point used by the dispatch layer.

    ``fmt`` names the FormatPolicy (None infers it from ``dtype_in``);
    it is part of the cache key, so the same shape planned under two
    formats yields two independent plans.
    """
    dtype_out = dtype_out if dtype_out is not None else dtype_in
    sig = GemmSignature.make(m, n, k, dtype_in, dtype_out, epilogue,
                             policy, backend, group, fmt)
    return _GLOBAL.plan(sig, measure=measure, interpret=interpret)


def plan_with_geometry(m: int, n: int, k: int, dtype_in, dtype_out=None, *,
                       epilogue: Optional[Epilogue] = None,
                       policy: Policy = "mte", backend: str = "pallas",
                       group: int = 1, fmt: Optional[str] = None,
                       geometry: BlockGeometry) -> ExecutionPlan:
    """A plan pinned to an explicit block geometry — no cache interaction.

    This is the program-level scheduling hook (:mod:`repro.graph.schedule`):
    a compiled program may trade the per-GEMM-optimal cached plan for a
    program-optimal one (e.g. a tile shape kept stable across a fused
    chain), and executes it by pinning the geometry here instead of
    re-entering the planner.  The route is re-derived from the geometry so
    split-K / grouped overrides launch the right kernel.
    """
    dtype_out = dtype_out if dtype_out is not None else dtype_in
    sig = GemmSignature.make(m, n, k, dtype_in, dtype_out, epilogue,
                             policy, backend, group, fmt)
    plan = ExecutionPlan(signature=sig, geometry=geometry,
                         route=_route_for(sig, geometry),
                         predicted_s=score_geometry(
                             sig, geometry, _GLOBAL.profile, _GLOBAL.n_cores),
                         source="program")
    _note_plan(sig, "program", plan.predicted_s)
    return plan


def save_plans(path: str) -> None:
    _GLOBAL.save(path)


def load_plans(path: str) -> int:
    return _GLOBAL.load(path)


# ---------------------------------------------------------------------------
# Benchmark helper (benchmarks/run.py): fixed analytic plan vs autotuned
# ---------------------------------------------------------------------------


def benchmark_shape(m: int, n: int, k: int, dtype_in="float32", *,
                    iters: int = 3,
                    interpret: Optional[bool] = None) -> Dict[str, float]:
    """Time the fixed analytic plan against the measured autotune winner.

    Both run through the same kernel launcher on the current substrate, so
    the comparison is apples-to-apples; the autotuned winner is by
    construction the fastest measured candidate (the analytic plan is in
    the candidate set), keeping the regression bound trivially satisfied
    up to timer noise.
    """
    sig = GemmSignature.make(m, n, k, dtype_in, "float32")
    cache = PlanCache(profile=_GLOBAL.profile, n_cores=_GLOBAL.n_cores)
    cands = enumerate_candidates(sig, cache.profile, cache.n_cores)
    analytic = ExecutionPlan(
        signature=sig, geometry=cands[0], route=_route_for(sig, cands[0]),
        predicted_s=score_geometry(sig, cands[0], cache.profile,
                                   cache.n_cores))
    tuned = cache.plan(sig, measure=True, interpret=interpret)
    t_analytic = measure_plan(analytic, iters=iters, interpret=interpret)
    if (tuned.geometry == analytic.geometry
            and tuned.route == analytic.route):
        t_tuned = t_analytic  # same plan won: identical by definition
    else:
        # One fresh measurement each, same iters — apples to apples.
        t_tuned = measure_plan(tuned, iters=iters, interpret=interpret)
    return {
        "analytic_us": t_analytic * 1e6,
        "autotuned_us": t_tuned * 1e6,
        "speedup": t_analytic / max(t_tuned, 1e-12),
        "route": tuned.route,
        "plan": tuned.describe(),
    }


def benchmark_format(m: int, n: int, k: int, fmt: str = "fp32", *,
                     iters: int = 3, measure: bool = True,
                     interpret: Optional[bool] = None) -> Dict[str, float]:
    """Model + (optionally) measure one shape under one FormatPolicy.

    The modeled time comes from the analytic score of the format's best
    candidate — this is where the narrower-SEW throughput/traffic gains
    show up regardless of substrate.  The measured time runs the tuned
    winner on the current substrate (interpret mode on CPU has no native
    int8 MMA, so CPU-measured int8 numbers reflect the interpreter, not
    the TPU target; the modeled column is the paper-faithful comparison).
    Measurement excludes the quantize/dequantize halves: weights are
    quantized once offline in the serving scenario this models.
    """
    from repro.core.formats import FORMATS
    fp = FORMATS[fmt]
    sig = GemmSignature.make(m, n, k, fp.operand_dtype, fp.accum_dtype,
                             fmt=fmt)
    cache = PlanCache(profile=_GLOBAL.profile, n_cores=_GLOBAL.n_cores)
    # Modeled = the analytic best over the format's candidate set — a
    # substrate-independent number (measured refinement may route to a
    # different winner on this substrate without changing it).
    cands = enumerate_candidates(sig, cache.profile, cache.n_cores)
    modeled = min(score_geometry(sig, g, cache.profile, cache.n_cores)
                  for g in cands)
    plan = cache.plan(sig, measure=measure, interpret=interpret)
    out = {
        "fmt": fmt,
        "modeled_us": modeled * 1e6,
        "route": plan.route,
        "plan": plan.describe(),
    }
    if measure:
        out["measured_us"] = measure_plan(plan, iters=iters,
                                          interpret=interpret) * 1e6
    return out
