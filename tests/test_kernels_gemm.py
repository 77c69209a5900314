"""Pallas GEMM kernels vs pure-jnp oracles (interpret mode, shape/dtype sweep)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.epilogue import Epilogue
from repro.kernels import ops, ref

RNG = np.random.default_rng(0)

SHAPES = [
    (8, 8, 8), (64, 64, 64), (128, 128, 128),       # aligned
    (100, 70, 130), (33, 257, 65), (513, 129, 255),  # ragged everything
    (16, 512, 96), (1024, 16, 64), (8, 2048, 8),     # tall / skinny / small
    (300, 33, 7), (7, 9, 1000),                      # tiny M/N, deep K
]


def _mats(m, n, k, dtype=np.float32):
    a = RNG.standard_normal((m, k)).astype(dtype)
    b = RNG.standard_normal((k, n)).astype(dtype)
    return jnp.asarray(a), jnp.asarray(b)


@pytest.mark.parametrize("m,n,k", SHAPES)
def test_mte_gemm_fp32_sweep(m, n, k):
    a, b = _mats(m, n, k)
    out = ops.mte_gemm(a, b)
    want = ref.mte_gemm(a, b)
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("m,n,k", [(64, 64, 64), (100, 70, 130), (16, 512, 96)])
def test_mte_gemm_bf16_mixed_precision(m, n, k):
    """tfwmul: SEW_i=16 → SEW_o=32 with Formula 3 transposed-B layout."""
    a, b = _mats(m, n, k)
    a, b = a.astype(jnp.bfloat16), b.astype(jnp.bfloat16)
    out = ops.mte_gemm(a, b)
    assert out.dtype == jnp.float32
    want = ref.mte_gemm(a, b)
    np.testing.assert_allclose(np.float32(out), np.float32(want),
                               rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("epi", [
    Epilogue(),
    Epilogue(alpha=2.5),
    Epilogue(alpha=0.5, beta=1.5),
    Epilogue(has_bias=True),
    Epilogue(activation="relu"),
    Epilogue(activation="gelu", has_bias=True),
    Epilogue(alpha=0.3, beta=2.0, has_bias=True, activation="silu"),
    Epilogue(softcap=30.0),
    Epilogue(alpha=1.2, softcap=50.0, activation="tanh"),
])
def test_fused_epilogue_matrix_vector_interplay(epi):
    """§III-C4: the whole BLAS epilogue fuses into the kernel."""
    m, n, k = 96, 144, 48
    a, b = _mats(m, n, k)
    c = jnp.asarray(RNG.standard_normal((m, n)).astype(np.float32))
    bias = jnp.asarray(RNG.standard_normal(n).astype(np.float32))
    out = ops.mte_gemm(a, b, c if epi.needs_c_input else None,
                       bias if epi.has_bias else None, epilogue=epi)
    want = ref.mte_gemm(a, b, c if epi.needs_c_input else None,
                        bias if epi.has_bias else None, epilogue=epi)
    np.testing.assert_allclose(out, want, rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("m,n,k", [(64, 64, 64), (100, 70, 130), (16, 512, 96)])
def test_rigid_amx_baseline_matches(m, n, k):
    """The AMX-semantics baseline must agree numerically — it is only
    *slower* (separate epilogue pass), never different."""
    a, b = _mats(m, n, k)
    epi = Epilogue(alpha=0.5, has_bias=True, activation="gelu")
    bias = jnp.asarray(RNG.standard_normal(n).astype(np.float32))
    out = ops.mte_gemm(a, b, bias=bias, epilogue=epi, policy="amx")
    want = ref.mte_gemm(a, b, bias=bias, epilogue=epi)
    np.testing.assert_allclose(out, want, rtol=3e-5, atol=3e-5)


def test_int8_quantized_gemm():
    a = jnp.asarray(RNG.integers(-100, 100, (64, 96)), jnp.int8)
    b = jnp.asarray(RNG.integers(-100, 100, (96, 128)), jnp.int8)
    out = ops.mte_gemm(a, b, out_dtype=jnp.int32)
    want = jnp.asarray(a, jnp.int32) @ jnp.asarray(b, jnp.int32)
    np.testing.assert_array_equal(out, want)


@pytest.mark.parametrize("g,cap,k,n", [(4, 40, 64, 96), (8, 16, 32, 128),
                                       (2, 100, 17, 33), (16, 8, 512, 64)])
def test_grouped_gemm_sweep(g, cap, k, n):
    x = jnp.asarray(RNG.standard_normal((g, cap, k)).astype(np.float32))
    w = jnp.asarray(RNG.standard_normal((g, k, n)).astype(np.float32))
    epi = Epilogue(activation="silu")
    out = ops.grouped_gemm(x, w, epilogue=epi)
    want = ref.grouped_gemm(x, w, epilogue=epi)
    np.testing.assert_allclose(out, want, rtol=3e-5, atol=3e-5)


def test_policy_changes_schedule_not_results():
    """Different geometry policies are bit-compatible up to fp reassociation."""
    a, b = _mats(130, 70, 100)
    outs = [np.asarray(ops.mte_gemm(a, b, policy=p))
            for p in ("mte", "amx", "vector", "sifive")]
    for o in outs[1:]:
        np.testing.assert_allclose(o, outs[0], rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("m,n,k,splits", [
    (16, 128, 2048, 4),     # decode GEMV-ish: tiny (M,N) grid, deep K
    (64, 64, 1000, 3),      # ragged K not divisible by splits
    (8, 256, 64, 4),        # K smaller than splits*bk (degenerate)
    (100, 70, 513, 2),
])
def test_splitk_gemm(m, n, k, splits):
    """Split-K (the 'vectorize all three loops' axis): partials + fused
    reduction must equal the plain kernel."""
    from repro.core.geometry import solve_block_geometry
    from repro.core.tile_state import SEW
    from repro.kernels.splitk_gemm import mte_gemm_splitk_pallas
    a, b = _mats(m, n, k)
    geom = solve_block_geometry(m, n, k, SEW.E32, SEW.E32)
    epi = Epilogue(alpha=0.5, activation="relu")
    out = mte_gemm_splitk_pallas(a, b, geom=geom, n_split=splits,
                                 epilogue=epi, interpret=True)
    want = ref.mte_gemm(a, b, epilogue=epi)
    np.testing.assert_allclose(out, want, rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("splits", [2, 4, 8])
@pytest.mark.parametrize("m,n,k", [(16, 128, 2048), (4, 96, 1000),
                                   (32, 64, 515)])
def test_splitk_nsplit_sweep_ragged_k(m, n, k, splits):
    """n_split ∈ {2,4,8} across ragged K, with fused c/bias epilogue."""
    from repro.core.geometry import solve_block_geometry
    from repro.core.tile_state import SEW
    from repro.kernels.splitk_gemm import mte_gemm_splitk_pallas
    a, b = _mats(m, n, k)
    c = jnp.asarray(RNG.standard_normal((m, n)).astype(np.float32))
    bias = jnp.asarray(RNG.standard_normal(n).astype(np.float32))
    geom = solve_block_geometry(m, n, k, SEW.E32, SEW.E32)
    epi = Epilogue(alpha=0.7, beta=1.3, has_bias=True, activation="gelu")
    out = mte_gemm_splitk_pallas(a, b, c, bias, geom=geom, n_split=splits,
                                 epilogue=epi, interpret=True)
    want = ref.mte_gemm(a, b, c, bias, epilogue=epi)
    np.testing.assert_allclose(out, want, rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("splits", [2, 4, 8])
def test_splitk_bf16_mixed_precision(splits):
    """tfwmul through split-K: bf16 inputs, f32 partials/output."""
    from repro.core.geometry import solve_block_geometry
    from repro.core.tile_state import SEW
    from repro.kernels.splitk_gemm import mte_gemm_splitk_pallas
    m, n, k = 16, 128, 1536
    a, b = _mats(m, n, k)
    a, b = a.astype(jnp.bfloat16), b.astype(jnp.bfloat16)
    geom = solve_block_geometry(m, n, k, SEW.E16, SEW.E32)
    geom = dataclasses.replace(geom, transposed_b=False)
    out = mte_gemm_splitk_pallas(a, b, geom=geom, n_split=splits,
                                 interpret=True)
    assert out.dtype == jnp.float32
    want = ref.mte_gemm(a, b)
    np.testing.assert_allclose(np.float32(out), np.float32(want),
                               rtol=2e-2, atol=2e-2)


def test_splitk_route_is_differentiable():
    """The plan-cached split-K route must carry gradients like the plain
    MTE route (backward = two more plan-cached GEMMs)."""
    from repro.core import autotune
    autotune.reset_cache()
    m, n, k = 16, 256, 4096  # routes to split-K (see test_autotune)
    a, b = _mats(m, n, k)
    assert autotune.get_plan(m, n, k, jnp.float32).route == "splitk"

    def f_kernel(a_, b_):
        return jnp.sum(ops.mte_gemm(a_, b_) ** 2)

    def f_ref(a_, b_):
        return jnp.sum(ref.mte_gemm(a_, b_) ** 2)

    ga_k, gb_k = jax.grad(f_kernel, argnums=(0, 1))(a, b)
    ga_r, gb_r = jax.grad(f_ref, argnums=(0, 1))(a, b)
    np.testing.assert_allclose(ga_k, ga_r, rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(gb_k, gb_r, rtol=2e-3, atol=2e-3)
    autotune.reset_cache()


def test_solver_enables_splitk_when_grid_underfills():
    from repro.core.geometry import solve_block_geometry
    from repro.core.tile_state import SEW
    g = solve_block_geometry(16, 128, 65536, SEW.E32, SEW.E32, n_cores=8)
    assert g.split_k > 1  # tiny (M,N) grid + deep K → split
    g2 = solve_block_geometry(8192, 8192, 8192, SEW.E32, SEW.E32, n_cores=8)
    assert g2.split_k == 1  # grid already fills the cores
