"""Analytical machine model for the evaluated architectures (paper §V-E).

The paper's performance numbers come from a trace-driven simulator that
models physical register allocation, cache-level data movement, and the two
instruction cost components of Table VII:

- a **static** front-end latency, overlappable with other instructions, and
- a **dynamic** latency tied to vector length/compute throughput that
  blocks the compute resource.

This module is the reproduction's equivalent: a closed-form model of the
same effects, driven by the kernel structure (tile geometry + unroll plan
from :mod:`repro.core.geometry`) instead of an instruction trace.  The model
computes, per GEMM:

``cycles = max(compute, memory, issue)`` where

- ``compute``: MMA count × per-MMA occupancy.  A dependent accumulation
  chain can only issue one MMA per (static + dynamic) cycles, so with
  ``n_indep`` live accumulator tiles the effective inverse throughput is
  ``max(dynamic / n_units, (static + dynamic) / n_indep)`` — this is
  exactly the register-count mechanism the paper identifies: AMX's 8
  registers bound ``n_indep`` at 4 (2×2 unroll) while MTE₃₂'s 32 registers
  sustain 16-20 chains.
- ``memory``: tile-load traffic through the L2 + DRAM re-stream traffic for
  operand panels that exceed cache capacity (Table IV memory system).
- ``issue``: retired instructions / issue width (Table IV, 6-wide).

Efficiency = useful FLOPs / (cycles × 512 FLOP/cycle), matching the paper's
"percentage of peak performance" metric (all architectures share the same
1024 GFLOP/s fp32 peak, §V-A).

The TPU-side analogue (`tpu_gemm_time`) applies the identical structure to
the v5e profile for the Pallas kernel schedules: MXU pass occupancy versus
HBM traffic, used by the kernel-geometry hillclimb and the gemm showcase.
"""
from __future__ import annotations

import dataclasses
from typing import Dict

from repro.core.geometry import (
    BlockGeometry, HardwareProfile, PROFILES, TPU_V5E, TpuProfile, cdiv,
    max_tile_dims, sifive_tile_dims, solve_unroll, round_up,
)
from repro.core.isa import count_instructions
from repro.core.tile_state import SEW

__all__ = ["GemmTiming", "model_gemm", "model_all", "tpu_gemm_time",
           "analytic_seconds", "set_calibration", "clear_calibration",
           "calibration", "calibrated_seconds"]


@dataclasses.dataclass(frozen=True)
class GemmTiming:
    arch: str
    m: int
    n: int
    k: int
    cycles: float
    compute_cycles: float
    memory_cycles: float
    issue_cycles: float
    useful_flops: int
    padded_flops: int

    @property
    def efficiency(self) -> float:
        profile = PROFILES[self.arch]
        return self.useful_flops / (self.cycles * profile.flops_per_cycle)

    @property
    def gflops(self) -> float:
        profile = PROFILES[self.arch]
        secs = self.cycles / profile.freq_hz
        return self.useful_flops / secs / 1e9

    @property
    def seconds(self) -> float:
        return self.cycles / PROFILES[self.arch].freq_hz

    @property
    def bottleneck(self) -> str:
        parts = {"compute": self.compute_cycles, "memory": self.memory_cycles,
                 "issue": self.issue_cycles}
        return max(parts, key=parts.get)


def _tile_and_plan(profile: HardwareProfile, m, n, k, sew_i, sew_o):
    if profile.name == "sifiveint":
        tile = sifive_tile_dims(profile, sew_i)
    else:
        tile = max_tile_dims(profile, sew_i, sew_o)
    plan = solve_unroll(profile, tile, m, n, k)
    return tile, plan


def model_gemm(arch: str, m: int, n: int, k: int,
               sew_i: SEW = SEW.E32, sew_o: SEW = SEW.E32,
               with_beta: bool = True) -> GemmTiming:
    """Model one GEMM's execution on one of the Table VII architectures."""
    profile = PROFILES[arch]
    sew = sew_i
    useful_flops = 2 * m * n * k

    if profile.rlen_bits == 0:
        # --- vector ISA: vectorize N, unroll M ---------------------------
        vl = profile.max_vl_elems(sew)
        um = max(1, min(profile.arch_regs - 2, m))
        nt, mt = cdiv(n, vl), cdiv(m, um)
        kt = k
        n_mma = mt * nt * k * um          # vfmacc instructions
        flops_per_mma = 2 * vl            # padded: full VL occupied
        n_indep = um
        # per K step: one B-row vector load (A comes via scalar broadcast)
        loads = [(mt * nt * k, min(n, vl) * sew.bytes)]
        c_moves = mt * nt * um * (2 if with_beta else 1)
        loads_c_bytes = min(n, vl) * sew_o.bytes
        macro_m, macro_n = um, vl
    else:
        tile, plan = _tile_and_plan(profile, m, n, k, sew_i, sew_o)
        um, un = plan.um, plan.un
        mt = cdiv(m, tile.m * um)
        nt = cdiv(n, tile.n * un)
        kt = cdiv(k, tile.k)
        n_mma = mt * nt * kt * um * un
        flops_per_mma = tile.flops
        n_indep = plan.indep_chains
        a_tile_bytes = tile.m * tile.k * sew_i.bytes
        b_tile_bytes = tile.k * tile.n * sew_i.bytes
        loads = [(mt * nt * kt * um, a_tile_bytes),
                 (mt * nt * kt * un, b_tile_bytes)]
        c_moves = mt * nt * um * un * (2 if with_beta else 1)
        loads_c_bytes = tile.m * tile.n * sew_o.bytes
        macro_m, macro_n = tile.m * um, tile.n * un

    padded_flops = n_mma * flops_per_mma

    # -- compute: dependency-limited vs resource-limited ---------------------
    # MTE32v's cvfma decomposition moves A operands across the lane
    # interconnect between steps (§IV-A2) — an occupancy overhead the
    # Table VII dynamic latency does not include.
    eff_dynamic = profile.dynamic_latency
    if profile.rlen_bits and not profile.systolic and profile.name == "mte32v":
        eff_dynamic = profile.dynamic_latency * 1.15
    per_mma = max(eff_dynamic / profile.n_units,
                  (profile.static_latency + profile.dynamic_latency)
                  / max(n_indep, 1))
    compute_cycles = n_mma * per_mma
    if not profile.systolic:
        # Vector-unit implementations (§IV-A2) execute tile moves, slides and
        # the vector-mode epilogue on the *same* VPUs as the cvfma compute —
        # the systolic variants run them on their dedicated side VPUs.  Each
        # vector op occupies a VPU for VLEN/lane-width cycles.
        move_cycles = profile.vlen_bits / 2048.0
        n_loads = sum(cnt for cnt, _ in loads)
        n_aux = n_loads + c_moves
        if profile.name == "sifiveint":
            n_aux += n_mma  # A-tile slides, one per MMA (see isa.py)
        compute_cycles += n_aux * move_cycles / profile.n_units

    # -- memory ---------------------------------------------------------------
    # L2→register tile-load port: sustained bandwidth is MSHR-limited
    # (profile.l2_bw) and each discrete load pays a minimum port occupancy —
    # tiny tile loads (SiFiveInt's 64 B A tiles) waste the port.
    min_occ = 4.0  # cycles
    l2_cycles = 0.0
    for count, nbytes in loads + [(c_moves, loads_c_bytes)]:
        l2_cycles += count * max(nbytes / profile.l2_bw_bytes_per_cycle, min_occ)

    # DRAM: cache-blocked panel streaming.  With the m→n→k loop nest of
    # Algorithm 1, the A row-panel (macro_m × K) is reused across the N sweep
    # if it fits in half the L2; the B column-panel (K × macro_n) is streamed
    # once per N iteration and reused across M if it fits.
    a_bytes = m * k * sew_i.bytes
    b_bytes = k * n * sew_i.bytes
    c_bytes = m * n * sew_o.bytes
    a_panel = macro_m * k * sew_i.bytes
    b_panel = k * macro_n * sew_i.bytes
    a_streams = 1 if a_panel <= profile.l2_bytes // 2 else max(1, cdiv(n, macro_n))
    b_streams = 1 if b_panel <= profile.l2_bytes // 2 else max(1, cdiv(m, macro_m))
    dram_bytes = (a_bytes * a_streams + b_bytes * b_streams
                  + c_bytes * (2 if with_beta else 1))
    dram_cycles = dram_bytes / profile.dram_bw_bytes_per_cycle
    memory_cycles = max(l2_cycles, dram_cycles)
    counts = count_instructions(arch, m, n, k, sew_i, sew_o, with_beta)

    # -- issue ---------------------------------------------------------------
    # Vector/matrix instructions plus ~30% scalar loop/address overhead.
    issue_cycles = counts.total * 1.3 / profile.issue_width

    cycles = max(compute_cycles, memory_cycles, issue_cycles)
    return GemmTiming(arch=arch, m=m, n=n, k=k, cycles=cycles,
                      compute_cycles=compute_cycles,
                      memory_cycles=memory_cycles,
                      issue_cycles=issue_cycles,
                      useful_flops=useful_flops, padded_flops=padded_flops)


def model_all(m: int, n: int, k: int, sew_i: SEW = SEW.E32,
              sew_o: SEW = SEW.E32) -> Dict[str, GemmTiming]:
    return {a: model_gemm(a, m, n, k, sew_i, sew_o) for a in PROFILES}


# ---------------------------------------------------------------------------
# TPU kernel-schedule model (the hardware-adapted side)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TpuGemmTiming:
    geom: BlockGeometry
    m: int
    n: int
    k: int
    compute_s: float
    memory_s: float
    useful_flops: int
    padded_flops: int
    hbm_bytes: int
    profile: TpuProfile = TPU_V5E

    @property
    def seconds(self) -> float:
        return max(self.compute_s, self.memory_s)

    @property
    def efficiency(self) -> float:
        peak = self.profile.peak_flops(self.geom.sew_i)
        return self.useful_flops / (self.seconds * peak)

    @property
    def bottleneck(self) -> str:
        return "compute" if self.compute_s >= self.memory_s else "memory"


def tpu_gemm_time(geom: BlockGeometry, m: int, n: int, k: int,
                  profile: TpuProfile = TPU_V5E,
                  n_cores: int = 1) -> TpuGemmTiming:
    """Model a Pallas block schedule on the TPU profile.

    compute: padded FLOPs (block-rounded dims) / MXU peak — padding waste is
    the rigid-geometry penalty, just as in the CPU model.
    memory: HBM traffic of the grid schedule: A tiles are streamed once per
    N-block column, B tiles once per M-block row, C written once (plus read
    when beta != 0 handled by caller).

    The geometry's SEW pair makes this model **format-aware**: narrower
    operand SEWs raise the attainable MXU rate (E8 int ops run at 2x the
    E16 rate, ``TpuProfile.peak_flops``) *and* shrink the A/B HBM bytes
    by ``sew_i.bytes`` — so the same (M, N, K) scores differently per
    :class:`repro.core.formats.FormatPolicy`, which is what lets the plan
    cache rank int8 above fp32 on the decode shapes.

    ``n_cores`` models grid occupancy across a multi-core slice: the
    parallel work units of a schedule are the ``gm·gn·split_k`` independent
    output (or partial) tiles — the K loop within one tile is a sequential
    accumulation chain.  When fewer parallel tiles exist than cores, both
    the attainable FLOP rate and the aggregate HBM streaming rate scale by
    the occupancy fraction; this is the term that makes split-K profitable
    for the paper's tall/skinny shapes (M or N ≤ 32, deep K), where the
    (M, N) grid alone leaves most of the machine idle.  ``n_cores=1``
    (default) reproduces the single-core model exactly.
    """
    gm, gn, gk = geom.grid_for(m, n, k)
    pm, pn, pk = gm * geom.bm, gn * geom.bn, gk * geom.bk
    padded_flops = 2 * pm * pn * pk
    useful_flops = 2 * m * n * k
    peak = profile.peak_flops(geom.sew_i)
    parallel_tiles = gm * gn * max(geom.split_k, 1)
    occupancy = min(1.0, parallel_tiles / max(n_cores, 1))
    compute_s = padded_flops / (peak * occupancy)

    a_bytes = pm * pk * geom.sew_i.bytes * gn     # A re-streamed per N column
    b_bytes = pk * pn * geom.sew_i.bytes * gm     # B re-streamed per M row
    c_bytes = pm * pn * geom.sew_o.bytes
    if geom.split_k > 1:
        c_bytes += pm * pn * 4 * geom.split_k      # f32 partials round-trip
    hbm = a_bytes + b_bytes + c_bytes
    memory_s = hbm / (profile.hbm_bw_bytes_per_s * occupancy)

    return TpuGemmTiming(geom=geom, m=m, n=n, k=k, compute_s=compute_s,
                         memory_s=memory_s, useful_flops=useful_flops,
                         padded_flops=padded_flops, hbm_bytes=hbm,
                         profile=profile)


def analytic_seconds(m: int, n: int, k: int, *, fmt: str = "fp32",
                     policy: str = "mte", group: int = 1,
                     profile: TpuProfile = TPU_V5E,
                     n_cores: int = 1) -> float:
    """Predicted seconds for a dispatch that never consulted the planner.

    The dispatch seams that bypass the plan cache (the plain-XLA dot in
    ``dispatch.mte_gemm``, the rigid ``policy='amx'`` baseline in
    ``kernels/ops.py``) still need a perf-model prediction so the
    profiler's calibration table can score them — this solves the
    analytic block geometry for the shape/format and returns its
    modeled time, the exact number ``PlanCache`` would have predicted
    for its analytic base candidate.  Grouped dispatches are modeled as
    ``group`` sequential per-member schedules (the grouped kernel's
    group grid dimension is already parallelism).
    """
    from repro.core.formats import FORMATS
    from repro.core.geometry import solve_block_geometry
    fp = FORMATS.get(fmt)
    sew_i = fp.sew_i if fp is not None else SEW.E32
    sew_o = fp.sew_o if fp is not None else SEW.E32
    solver_policy = policy if policy in ("mte", "amx") else "mte"
    geom = solve_block_geometry(m, n, k, sew_i, sew_o, profile=profile,
                                policy=solver_policy)
    t = tpu_gemm_time(geom, m, n, k, profile=profile, n_cores=n_cores)
    return t.seconds * max(int(group), 1)


# ---------------------------------------------------------------------------
# Measured calibration scales (ROADMAP item 5, the measurement half)
# ---------------------------------------------------------------------------
#
# The analytic model above predicts; the telemetry profiler
# (repro.telemetry.profiler) measures.  Where they disagree, the profiler
# can install a per-(shape_class, fmt) measured/modeled ratio here so any
# consumer that wants substrate-honest predictions multiplies through
# ``calibrated_seconds``.  Nothing in the planner consumes these yet —
# plan ranking stays analytic and deterministic; the table is the
# evidence base the future tile simulator (ROADMAP item 5's remaining
# half) will be validated against.

_CALIBRATION: Dict[tuple, float] = {}


def set_calibration(shape_class: str, fmt: str, ratio: float) -> None:
    """Record a measured/modeled error ratio for one (shape class, fmt)."""
    ratio = float(ratio)
    if not (ratio > 0.0) or ratio != ratio or ratio == float("inf"):
        raise ValueError(f"calibration ratio must be finite and positive, "
                         f"got {ratio!r} for ({shape_class}, {fmt})")
    _CALIBRATION[(str(shape_class), str(fmt))] = ratio


def clear_calibration() -> None:
    _CALIBRATION.clear()


def calibration() -> Dict[str, float]:
    """The installed ratios as ``{"shape_class/fmt": ratio}`` (a copy)."""
    return {f"{sc}/{fmt}": r for (sc, fmt), r in sorted(_CALIBRATION.items())}


def calibrated_seconds(seconds: float, shape_class: str, fmt: str) -> float:
    """Scale an analytic prediction by the installed measured ratio
    (identity when no ratio has been installed for the class/format)."""
    return float(seconds) * _CALIBRATION.get((str(shape_class), str(fmt)),
                                             1.0)
