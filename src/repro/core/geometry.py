"""MTE tile-geometry solver (paper §III-A) and its TPU generalization.

Two levels of geometry live here:

1. **Register-level geometry** — the paper's Formulas 2 and 3 verbatim.
   Given ``VLEN``, ``RLEN`` and element widths ``SEW_i``/``SEW_o`` they
   yield the maximum hardware tile shape (M, N, K).  On top of that, the
   *unroll solver* reproduces the paper's software optimization (§III-D,
   §VI-A2): unroll the M/N loops so multiple C accumulator tiles are live
   simultaneously, bounded by the number of architecturally visible
   registers (32 for MTE₃₂, 8 for MTE₈ₛ/AMX).  This is the mechanism behind
   the paper's 1.35× over AMX and is what :mod:`repro.core.isa` (Table IX)
   and :mod:`repro.core.perfmodel` (Fig. 7/8) consume.

2. **VMEM-level geometry** — the TPU adaptation.  On a TPU the "vector
   register file" role is played by VMEM and the MXU defines the native
   tile granularity (128 lanes; 8/16/32 sublanes for 32/16/8-bit types).
   ``solve_block_geometry`` maps a logical GEMM (M, N, K, dtypes) onto
   Pallas ``BlockSpec`` tiles exactly the way Formula 2/3 maps a GEMM onto
   vector registers: the tile shape is *derived from hardware constants +
   requested shape*, never hard-coded — that is the paper's
   geometry-agnosticism transplanted to TPU.

Policies model the paper's evaluated architectures:

- ``mte``     — geometry-agnostic (the proposal; 32-register / full-VMEM
                budget, fused epilogue allowed).
- ``amx``     — rigid 16×16(×SEW) tiles, 8 architectural tile registers,
                epilogue through memory (models Intel AMX, a.k.a. MTE₈ₛ).
- ``sifive``  — 4×4 A-tile semantics (models SiFiveInt): tiny A panel.
- ``vector``  — vectorize N only (models Vector 1KB/2KB RISC-V V kernels).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Literal, Optional, Tuple

from repro.core.tile_state import SEW, TileState

__all__ = [
    "HardwareProfile",
    "TpuProfile",
    "RegisterTile",
    "UnrollPlan",
    "BlockGeometry",
    "PROFILES",
    "TPU_V5E",
    "TPU_PROFILES",
    "device_profile",
    "max_tile_dims",
    "solve_unroll",
    "solve_block_geometry",
    "round_up",
    "cdiv",
]

Policy = Literal["mte", "amx", "sifive", "vector"]


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def round_up(x: int, m: int) -> int:
    return cdiv(x, m) * m


# ---------------------------------------------------------------------------
# CPU architecture profiles (paper Tables IV, V, VI, VII)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class HardwareProfile:
    """One evaluated architecture row of Table VII (+ system params, Table IV)."""

    name: str
    vlen_bits: int                 # vector register length
    rlen_bits: int                 # tile row length (0 => pure vector ISA)
    arch_regs: int                 # architecturally visible registers
    phys_regs: int                 # physical registers
    static_latency: int            # front-end latency, overlappable (cycles)
    dynamic_latency: int           # blocks the compute resource (cycles)
    n_units: int                   # VPUs (or 1 systolic array)
    systolic: bool
    freq_hz: float = 2.0e9
    flops_per_cycle: int = 512     # peak fp32 FLOP/cycle (all rows equal)
    # memory system (Table IV)
    l1_bytes: int = 48 * 1024
    l2_bytes: int = 2 * 1024 * 1024
    dram_bw_bytes_per_s: float = 191.25e9
    l1_bw_bytes_per_cycle: float = 128.0
    # Sustained tile-load bandwidth from L2: bounded by the L1's 10 MSHRs of
    # 128-byte lines over the 26-cycle L2 latency (Table IV) ≈ 48 B/cycle.
    # This is the resource that punishes low-unroll (8-register) kernels:
    # 2×2 unroll needs one 1 KiB tile load per MMA (21 cycles at 48 B/c > the
    # 16-cycle MMA) while 4×4 needs half that — the paper's register-count
    # mechanism (§VI-A2) expressed as load-port pressure.
    l2_bw_bytes_per_cycle: float = 48.0
    issue_width: int = 6

    @property
    def dram_bw_bytes_per_cycle(self) -> float:
        return self.dram_bw_bytes_per_s / self.freq_hz

    @property
    def peak_flops(self) -> float:
        return self.flops_per_cycle * self.freq_hz

    def max_vl_elems(self, sew: SEW) -> int:
        return self.vlen_bits // sew.bits


# Table VII rows.
PROFILES = {
    "vector1k": HardwareProfile(
        name="vector1k", vlen_bits=8192, rlen_bits=0, arch_regs=32,
        phys_regs=40, static_latency=20, dynamic_latency=4, n_units=4,
        systolic=False),
    "vector2k": HardwareProfile(
        name="vector2k", vlen_bits=16384, rlen_bits=0, arch_regs=32,
        phys_regs=40, static_latency=20, dynamic_latency=8, n_units=4,
        systolic=False),
    "sifiveint": HardwareProfile(
        name="sifiveint", vlen_bits=8192, rlen_bits=2048, arch_regs=32,
        phys_regs=40, static_latency=28, dynamic_latency=16, n_units=4,
        systolic=False),
    "mte8s": HardwareProfile(
        name="mte8s", vlen_bits=8192, rlen_bits=512, arch_regs=8,
        phys_regs=24, static_latency=36, dynamic_latency=16, n_units=1,
        systolic=True),
    "mte32s": HardwareProfile(
        name="mte32s", vlen_bits=8192, rlen_bits=512, arch_regs=32,
        phys_regs=40, static_latency=36, dynamic_latency=16, n_units=1,
        systolic=True),
    "mte32v": HardwareProfile(
        name="mte32v", vlen_bits=8192, rlen_bits=512, arch_regs=32,
        phys_regs=40, static_latency=36, dynamic_latency=64, n_units=4,
        systolic=False),
}


# ---------------------------------------------------------------------------
# Formula 2 / Formula 3 — maximum hardware tile dimensions
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class RegisterTile:
    """Maximum hardware tile geometry granted by the microarchitecture."""

    m: int
    n: int
    k: int
    transposed_b: bool  # mixed precision stores B col-major (paper §III-A2)

    @property
    def mnk(self) -> Tuple[int, int, int]:
        return (self.m, self.n, self.k)

    @property
    def macs(self) -> int:
        return self.m * self.n * self.k

    @property
    def flops(self) -> int:
        return 2 * self.macs


def max_tile_dims(profile: HardwareProfile, sew_i: SEW,
                  sew_o: Optional[SEW] = None) -> RegisterTile:
    """Formulas 2 (uniform) and 3 (mixed precision) from the paper.

    Uniform precision (SEW_i == SEW_o), row-major B::

        M = VLEN/RLEN,  N = RLEN/SEW,  K = min(M, N)

    Mixed precision (SEW_i < SEW_o), col-major ("transposed") B::

        M = VLEN/RLEN,  N = min(M, RLEN/SEW_o),  K = RLEN/SEW_i
    """
    sew_o = sew_o or sew_i
    if profile.rlen_bits == 0:
        # Pure vector ISA: degenerate 1 × VL × 1 geometry (Table VII).
        vl = profile.max_vl_elems(sew_i)
        return RegisterTile(m=1, n=vl, k=1, transposed_b=False)
    rows = profile.vlen_bits // profile.rlen_bits
    if sew_i == sew_o:
        m = rows
        n = profile.rlen_bits // sew_i.bits
        k = min(m, n)
        return RegisterTile(m=m, n=n, k=k, transposed_b=False)
    if sew_i.bits > sew_o.bits:
        raise ValueError("mixed precision requires SEW_i < SEW_o")
    m = rows
    n = min(m, profile.rlen_bits // sew_o.bits)
    k = profile.rlen_bits // sew_i.bits
    return RegisterTile(m=m, n=n, k=k, transposed_b=True)


def sifive_tile_dims(profile: HardwareProfile, sew_i: SEW) -> RegisterTile:
    """SiFiveInt per-instruction geometry: 4×4 A tile times all B tiles.

    With VLEN bits of B organized as independent 4×4 tiles the instruction
    geometry is M=4, K=4, N = 4 · (VLEN / (16·SEW)) — §V-C gives 4×64×4 for
    VLEN 8192, fp32.
    """
    tiles_in_reg = profile.vlen_bits // (16 * sew_i.bits)
    return RegisterTile(m=4, n=4 * tiles_in_reg, k=4, transposed_b=False)


# ---------------------------------------------------------------------------
# Register-level unroll solver (paper §III-D / §VI-A2)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class UnrollPlan:
    """Software loop-unroll plan for Algorithm 1.

    ``um``/``un`` count how many M-/N-direction tiles are processed per
    micro-kernel invocation; ``um*un`` C accumulator tiles, ``um`` A tiles
    and one (streamed) B tile are live simultaneously.  Register budget:
    ``um*un + um + 1 <= arch_regs`` (the paper's register-pressure model —
    AMX's 8 registers cap this at 2×2, MTE₃₂'s 32 allow 4×5/5×4).
    """

    tile: RegisterTile
    um: int
    un: int
    policy: Policy

    @property
    def live_regs(self) -> int:
        return self.um * self.un + self.um + 1

    @property
    def indep_chains(self) -> int:
        return self.um * self.un

    @property
    def macro_m(self) -> int:
        return self.tile.m * self.um

    @property
    def macro_n(self) -> int:
        return self.tile.n * self.un


def solve_unroll(profile: HardwareProfile, tile: RegisterTile,
                 m: int, n: int, k: int, policy: Policy = "mte") -> UnrollPlan:
    """Choose (um, un) for Algorithm 1's M/N loop unrolling.

    Mirrors the paper's JIT code generator (§III-D, §V-B1): unrolling serves
    two purposes — (i) expose enough *independent* tfmul chains to hide the
    static+dynamic instruction latency, and (ii) reuse the A/B tiles held in
    registers to cut tile-load traffic.  Objective: among plans whose
    independent-chain count covers the latency-hiding threshold, minimize
    load bytes per MMA ``(um·|A-tile| + un·|B-tile|) / (um·un)``; fall back
    to maximum chains when the budget cannot reach the threshold (the
    8-register / AMX case).  Useful tiles only: unrolling beyond
    ceil(dim/tile) adds no work.
    """
    budget = profile.arch_regs
    max_um = max(1, cdiv(m, max(tile.m, 1)))
    max_un = max(1, cdiv(n, max(tile.n, 1)))
    # Latency-hiding threshold: chains needed so a dependent accumulation
    # chain never starves the compute resource.
    threshold = cdiv((profile.static_latency + profile.dynamic_latency)
                     * profile.n_units, max(profile.dynamic_latency, 1))
    a_bytes = max(tile.m * tile.k, 1)
    b_bytes = max(tile.k * tile.n, 1)

    candidates = []
    for um in range(1, min(max_um, budget) + 1):
        for un in range(1, min(max_un, budget) + 1):
            # Register pressure: um·un accumulators + A tiles + streamed B.
            # Budgets ≥ 16 double-buffer the A tiles and the B slot to hide
            # tile-load latency (the paper's JIT prefetch); the 8-register
            # AMX case has no headroom and single-buffers.
            if budget >= 16:
                live = um * un + 2 * um + 2
            else:
                live = um * un + um + 1
            if live > budget:
                continue
            candidates.append(UnrollPlan(tile=tile, um=um, un=un,
                                         policy=policy))
    assert candidates, "register budget cannot hold a single tile set"

    def pad_factor(p: UnrollPlan) -> float:
        pm = cdiv(m, p.macro_m) * p.macro_m
        pn = cdiv(n, p.macro_n) * p.macro_n
        return (pm * pn) / (m * n)

    def cost(p: UnrollPlan) -> float:
        loads = (p.um * a_bytes + p.un * b_bytes) / (p.um * p.un)
        return loads * pad_factor(p)

    covered = [p for p in candidates if p.indep_chains >= threshold]
    if covered:
        return min(covered, key=lambda p: (cost(p), -p.indep_chains))
    return max(candidates, key=lambda p: (p.indep_chains / pad_factor(p),
                                          -cost(p)))


# ---------------------------------------------------------------------------
# TPU (VMEM/MXU) level — the hardware-adapted geometry solver
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TpuProfile:
    """TPU hardware constants used by the VMEM-level solver and roofline.

    The lane/sublane pair is the TPU's ``RLEN`` analogue: the minimum
    addressable native tile is (sublane, lane) where sublane depends on the
    element width exactly as RLEN/SEW does in the paper.
    """

    name: str = "tpu_v5e"
    vmem_bytes: int = 16 * 1024 * 1024        # per-core VMEM
    vmem_budget_frac: float = 0.75            # leave headroom for spills
    lane: int = 128
    mxu: Tuple[int, int] = (128, 128)
    peak_bf16_flops: float = 197e12           # per chip
    peak_fp32_flops: float = 98.5e12
    peak_int8_ops: float = 394e12             # E8 operands: 2x the bf16 rate
    hbm_bw_bytes_per_s: float = 819e9
    ici_bw_bytes_per_s: float = 50e9          # per link
    hbm_bytes: int = 16 * 1024 * 1024 * 1024

    def sublane(self, sew: SEW) -> int:
        # 32-bit types pack 8 sublanes; 16-bit 16; 8-bit 32.
        return 8 * (32 // sew.bits) if sew.bits <= 32 else 8

    def min_tile(self, sew: SEW) -> Tuple[int, int]:
        return (self.sublane(sew), self.lane)

    def peak_flops(self, sew_i: SEW) -> float:
        """Peak MXU rate by input SEW — the narrower-SEW throughput gain
        the format policy buys (E8 int ops run at 2x the E16 rate)."""
        if sew_i.bits <= 8:
            return self.peak_int8_ops
        return self.peak_bf16_flops if sew_i.bits <= 16 else self.peak_fp32_flops


TPU_V5E = TpuProfile()

# TPU profiles by ``jax.Device.device_kind``.
TPU_PROFILES = {"TPU v5 lite": TPU_V5E}


def device_profile() -> TpuProfile:
    """The profile of the device JAX computes on.

    A TPU is looked up by its ``device_kind``: a kind missing from
    ``TPU_PROFILES`` raises instead of being planned as another chip.
    Off the TPU (the CPU, the Pallas interpreter) the described target
    stays v5e.
    """
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return TPU_V5E
    try:
        return TPU_PROFILES[dev.device_kind]
    except KeyError:
        raise ValueError(f"no TpuProfile for device kind "
                         f"{dev.device_kind!r}; add one to "
                         f"TPU_PROFILES") from None


@dataclasses.dataclass(frozen=True)
class BlockGeometry:
    """A solved Pallas block schedule for one GEMM.

    ``bm``/``bn``/``bk`` are the BlockSpec tile dims; ``split_k`` > 1 means
    the K loop is parallelized over the grid with f32 partial accumulators
    (the TPU analogue of the paper's "vectorize the K dimension");
    ``n_acc`` is how many C accumulator tiles stay resident in VMEM
    (the register-count story at VMEM level); ``transposed_b`` requests the
    col-major B layout of Formula 3.
    """

    bm: int
    bn: int
    bk: int
    split_k: int
    n_acc: int
    transposed_b: bool
    sew_i: SEW
    sew_o: SEW
    policy: Policy

    @property
    def grid(self) -> Tuple[int, int, int]:
        raise NotImplementedError("grid depends on problem dims; use grid_for")

    def grid_for(self, m: int, n: int, k: int) -> Tuple[int, int, int]:
        return (cdiv(m, self.bm), cdiv(n, self.bn), cdiv(k, self.bk))

    def vmem_bytes(self) -> int:
        a = self.bm * self.bk * self.sew_i.bytes
        b = self.bk * self.bn * self.sew_i.bytes
        acc = self.bm * self.bn * 4  # f32 accumulator scratch
        out = self.bm * self.bn * self.sew_o.bytes
        # Double-buffered inputs (Pallas pipelines the HBM→VMEM copies).
        return 2 * (a + b) + acc + out


def _fit_pow2(value: int, lo: int, hi: int) -> int:
    """Round ``value`` up to a power-of-two-ish tile in [lo, hi]."""
    v = max(lo, min(hi, round_up(value, lo)))
    # Prefer exact multiples of lo that are powers of two times lo.
    t = lo
    while t < v:
        t *= 2
    return min(t, hi)


def solve_block_geometry(
    m: int, n: int, k: int,
    sew_i: SEW, sew_o: SEW,
    profile: TpuProfile = TPU_V5E,
    policy: Policy = "mte",
    n_cores: int = 1,
) -> BlockGeometry:
    """VMEM-level geometry solver — Formula 2/3 generalized to the TPU.

    The paper's principle: tile shape is *granted* from hardware constants
    and the requested GEMM shape, never fixed.  Concretely:

    - ``amx`` policy models a rigid ISA: always (128, 128, 128·u) blocks
      with at most 8 live accumulators and no geometry adaptation — small or
      skinny GEMMs pay full padding waste, exactly like AMX's 16×16×SEW.
    - ``mte`` adapts: block dims snap to the (sublane, lane) native tile,
      shrink to the problem (no padding waste beyond one native tile), grow
      bk when M/N are small (K-vectorization), and split K across the grid
      when the (m, n) grid alone cannot fill the machine.
    """
    sub = profile.sublane(sew_i)
    lane = profile.lane
    transposed_b = sew_i.bits < sew_o.bits

    if policy == "amx":
        bm = bn = 128
        bk = 128
        return BlockGeometry(bm=bm, bn=bn, bk=bk, split_k=1, n_acc=8,
                             transposed_b=False, sew_i=sew_i, sew_o=sew_o,
                             policy=policy)
    if policy == "vector":
        # Vectorize N only: one sublane-row of C per step, full-N panels.
        bn = min(round_up(n, lane), 512)
        return BlockGeometry(bm=sub, bn=bn, bk=min(round_up(k, sub), 512),
                             split_k=1, n_acc=1, transposed_b=False,
                             sew_i=sew_i, sew_o=sew_o, policy=policy)
    if policy == "sifive":
        # Tiny A panel: bm fixed to one native sublane tile, wide N.
        bn = min(round_up(n, lane), 1024)
        return BlockGeometry(bm=sub, bn=bn, bk=sub, split_k=1, n_acc=4,
                             transposed_b=False, sew_i=sew_i, sew_o=sew_o,
                             policy=policy)

    # --- "mte": geometry-agnostic solve --------------------------------
    budget = int(profile.vmem_bytes * profile.vmem_budget_frac)

    # Snap to native tiles, shrink to problem size (tall/skinny adaptation).
    bm = _fit_pow2(m, sub, 512)
    bn = _fit_pow2(n, lane, 512)

    # Grow bk to raise arithmetic intensity while A+B double buffers fit.
    bk = sub
    def fits(bm_, bn_, bk_):
        g = BlockGeometry(bm=bm_, bn=bn_, bk=bk_, split_k=1, n_acc=1,
                          transposed_b=transposed_b, sew_i=sew_i, sew_o=sew_o,
                          policy="mte")
        return g.vmem_bytes() <= budget

    k_cap = min(round_up(k, sub), 2048)
    while bk * 2 <= k_cap and fits(bm, bn, bk * 2):
        bk *= 2

    # If the (m, n) grid underfills the cores, split K across the grid —
    # the TPU analogue of the paper's "vectorize all three GEMM loops".
    grid_mn = cdiv(m, bm) * cdiv(n, bn)
    split_k = 1
    if n_cores > 1 and grid_mn < n_cores and k > bk:
        split_k = min(cdiv(k, bk), cdiv(n_cores, max(grid_mn, 1)))

    # Accumulator residency: how many C tiles fit in the remaining VMEM —
    # this is the 32-vs-8 register story at VMEM level.
    base = BlockGeometry(bm=bm, bn=bn, bk=bk, split_k=split_k, n_acc=1,
                         transposed_b=transposed_b, sew_i=sew_i, sew_o=sew_o,
                         policy="mte")
    tile_bytes = bm * bn * 4
    spare = max(0, budget - base.vmem_bytes())
    n_acc = max(1, min(32, 1 + spare // max(tile_bytes, 1)))

    return dataclasses.replace(base, n_acc=n_acc)


def tile_state_for(geom: BlockGeometry, m: int, n: int, k: int,
                   rlenb: int = 64) -> TileState:
    """Produce the MTE CSR contents describing one macro-tile step.

    Bridges the TPU block schedule back to the paper's architectural state:
    the granted (tm, tn, tk) for a step are the active extents within the
    block, clamped by the CSR 12-bit fields.
    """
    return TileState(
        tm=min(geom.bm, m, 4096), tn=min(geom.bn, n, 4096),
        tk=min(geom.bk, k, 4096), sew_i=geom.sew_i, sew_o=geom.sew_o,
        rlenb=rlenb)
