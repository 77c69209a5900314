"""Formula 2/3 tile solvers + TPU BlockSpec solver invariants."""
try:
    import hypothesis.strategies as st
    from hypothesis import given, settings
except ImportError:  # hermetic env: run properties via the local shim
    from _hypothesis_fallback import given, settings, strategies as st

import pytest

from repro.core.geometry import (
    PROFILES, TPU_V5E, max_tile_dims, sifive_tile_dims, solve_block_geometry,
    solve_unroll,
)
from repro.core.tile_state import SEW


def test_formula2_paper_example():
    """§III-A2: VLEN 8192, RLEN 512, SEW 32 → 16×16×16 uniform."""
    t = max_tile_dims(PROFILES["mte32s"], SEW.E32)
    assert t.mnk == (16, 16, 16) and not t.transposed_b
    # full register utilization on all operands (paper: 256 elements)
    assert t.m * t.n == 256


def test_formula3_paper_example():
    """§III-A2: SEW_o=32, SEW_i=16 → 16×16×32 with transposed B."""
    t = max_tile_dims(PROFILES["mte32s"], SEW.E16, SEW.E32)
    assert t.mnk == (16, 16, 32) and t.transposed_b
    # 256 output elements, 512 input elements — full capacity
    assert t.m * t.n == 256 and t.k * t.n == 512 * 16 // 16


def test_vector_degenerate_geometry():
    """Table VII: vector ISAs have 1×VL×1 geometry."""
    assert max_tile_dims(PROFILES["vector1k"], SEW.E32).mnk == (1, 256, 1)
    assert max_tile_dims(PROFILES["vector2k"], SEW.E32).mnk == (1, 512, 1)


def test_sifive_geometry():
    """§V-C: VLEN 8192 fp32 → 4×64×4."""
    assert sifive_tile_dims(PROFILES["sifiveint"], SEW.E32).mnk == (4, 64, 4)


@settings(max_examples=150, deadline=None)
@given(m=st.integers(1, 8192), n=st.integers(1, 8192), k=st.integers(1, 8192),
       arch=st.sampled_from(["mte8s", "mte32s", "mte32v", "sifiveint"]))
def test_unroll_respects_register_budget(m, n, k, arch):
    prof = PROFILES[arch]
    tile = (sifive_tile_dims(prof, SEW.E32) if arch == "sifiveint"
            else max_tile_dims(prof, SEW.E32))
    plan = solve_unroll(prof, tile, m, n, k)
    assert plan.live_regs <= prof.arch_regs
    assert plan.um >= 1 and plan.un >= 1


def test_amx_register_budget_forces_smaller_unroll():
    """The 8-register AMX budget cannot reach the 32-register unroll —
    the mechanism behind the paper's 1.35× (§VI-A)."""
    t8 = max_tile_dims(PROFILES["mte8s"], SEW.E32)
    t32 = max_tile_dims(PROFILES["mte32s"], SEW.E32)
    p8 = solve_unroll(PROFILES["mte8s"], t8, 2048, 2048, 2048)
    p32 = solve_unroll(PROFILES["mte32s"], t32, 2048, 2048, 2048)
    assert p8.indep_chains < p32.indep_chains
    assert p8.live_regs <= 8


@settings(max_examples=150, deadline=None)
@given(m=st.integers(1, 65536), n=st.integers(1, 65536),
       k=st.integers(1, 65536),
       sew=st.sampled_from([SEW.E8, SEW.E16, SEW.E32]),
       policy=st.sampled_from(["mte", "amx", "vector", "sifive"]))
def test_block_geometry_invariants(m, n, k, sew, policy):
    sew_o = SEW.E32
    g = solve_block_geometry(m, n, k, sew, sew_o, policy=policy)
    # hardware alignment: lane multiple on N, sublane multiple on M
    assert g.bn % TPU_V5E.lane == 0 or g.bn >= n
    assert g.bm % TPU_V5E.sublane(sew) == 0 or g.bm >= m
    assert g.bm > 0 and g.bn > 0 and g.bk > 0
    if policy == "mte":
        # VMEM budget respected (the paper's register-capacity analogue)
        assert g.vmem_bytes() <= TPU_V5E.vmem_bytes * TPU_V5E.vmem_budget_frac
        # mixed precision flags transposed B (Formula 3)
        assert g.transposed_b == (sew.bits < sew_o.bits)
    if policy == "amx":
        assert (g.bm, g.bn, g.bk) == (128, 128, 128)  # rigid, by design


@settings(max_examples=100, deadline=None)
@given(m=st.integers(1, 4096), n=st.integers(1, 4096), k=st.integers(1, 4096))
def test_mte_adapts_small_dims_amx_does_not(m, n, k):
    """Geometry agnosticism: MTE blocks never exceed the (aligned) problem;
    the rigid baseline always pads to 128."""
    g = solve_block_geometry(m, n, k, SEW.E32, SEW.E32, policy="mte")
    assert g.bm <= max(8, -(-m // 8) * 8) * 2 or g.bm <= 512
    if m <= 8:
        assert g.bm == 8
    if n <= 128:
        assert g.bn == 128


class _Dev:
    def __init__(self, platform, kind):
        self.platform, self.device_kind = platform, kind


@pytest.mark.parametrize("platform,kind,want", [
    ("cpu", "cpu", TPU_V5E),                  # described target off-chip
    ("tpu", "TPU v5 lite", TPU_V5E),
    ("tpu", "TPU v99 imaginary", ValueError),
])
def test_device_profile_by_device_kind(monkeypatch, platform, kind, want):
    import jax
    from repro.core.geometry import device_profile
    monkeypatch.setattr(jax, "devices", lambda *a: [_Dev(platform, kind)])
    if want is ValueError:
        with pytest.raises(ValueError, match="v99"):
            device_profile()
    else:
        assert device_profile() is want
