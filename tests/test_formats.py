"""Data-format policy (ISSUE 2): mixed-precision SEW threaded through
ISA → plan cache → kernels → models → serving.

Tolerances (documented contract):

- **fp32** kernel routes vs the fp32 oracle: fp reassociation only
  (rtol/atol 3e-5).
- **bf16** (bf16 operands, f32 accumulation): operand rounding is
  2^-8-relative per element; accumulated over K the observed route error
  stays within 1% of the output magnitude (rtol 0.02 vs the fp32
  oracle), and within fp noise of the same-math bf16 oracle.
- **bf16acc** (bf16 accumulation): block-order-sensitive accumulation —
  bounded against the fp32 oracle at rtol 0.05; no exact oracle exists
  because bf16 addition does not reassociate.
- **int8-with-scales**: symmetric per-channel quantization gives
  ≈1/127-relative error per operand; the route is *bit-exact* vs the
  shared-quantizer jnp oracle and within 5% of the fp32 oracle
  magnitude.
- **gradients**: straight-through estimator — with a linear loss the
  grads of every format equal the fp32 grads exactly (0 ulp), because
  the backward always runs the full-precision residuals.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import autotune, formats
from repro.core import dispatch
from repro.core.epilogue import Epilogue
from repro.core.isa import count_sew_sweep
from repro.core.tile_state import SEW
from repro.kernels import ops, ref

RNG = np.random.default_rng(11)

# Tall / skinny / square — the shape sweep the acceptance criteria name.
SHAPES = [(256, 32, 64), (1, 512, 1024), (96, 96, 96)]


@pytest.fixture(autouse=True)
def fresh_cache():
    autotune.reset_cache()
    yield
    autotune.reset_cache()


def _mats(m, n, k):
    a = RNG.standard_normal((m, k)).astype(np.float32)
    b = RNG.standard_normal((k, n)).astype(np.float32)
    return jnp.asarray(a), jnp.asarray(b)


def _rel(x, want):
    return float(jnp.max(jnp.abs(x - want)) / jnp.max(jnp.abs(want)))


# -- policy plumbing ----------------------------------------------------------


def test_registry_and_sew_mapping():
    assert formats.FORMATS["int8"].sew_i == SEW.E8
    assert formats.FORMATS["int8"].sew_o == SEW.E32
    assert formats.FORMATS["bf16"].sew_i == SEW.E16
    assert formats.FORMATS["bf16acc"].sew_o == SEW.E16
    assert formats.resolve_format("bf16") is formats.BF16
    assert formats.resolve_format(None, jnp.bfloat16) is formats.BF16
    assert formats.resolve_format(None, jnp.int8) is formats.INT8
    assert formats.resolve_format(None, jnp.float32) is formats.FP32
    with pytest.raises(ValueError):
        formats.resolve_format("fp8")


def test_quantize_roundtrip_error_bound():
    x = jnp.asarray(RNG.standard_normal((64, 128)).astype(np.float32))
    q, scale = formats.quantize(x, contract_axis=1)
    assert q.dtype == jnp.int8 and scale.shape == (64, 1)
    back = q.astype(jnp.float32) * scale
    # Symmetric 127-step grid: per-element error ≤ scale/2.
    assert float(jnp.max(jnp.abs(back - x) / scale)) <= 0.5 + 1e-6


def test_native_int_operands_skip_scaling():
    x = jnp.asarray(RNG.integers(-100, 100, (8, 16)), jnp.int8)
    q, scale = formats.quantize(x, contract_axis=1)
    assert scale is None
    np.testing.assert_array_equal(q, x)


def test_wide_integer_operands_not_truncated():
    """int32 operands outside int8 range must not be wrapped mod 256 —
    they keep their width and accumulate exactly, as pre-format."""
    a = jnp.asarray([[300, -5]], jnp.int32)
    b = jnp.asarray([[2], [3]], jnp.int32)
    for be in ("pallas", "xla", "reference"):
        out = dispatch.mte_gemm(a, b, backend=be)
        assert int(np.asarray(out).ravel()[0]) == 585, be


# -- forward parity: kernel routes vs oracles ---------------------------------


@pytest.mark.parametrize("m,n,k", SHAPES)
def test_int8_forward_parity(m, n, k):
    a, b = _mats(m, n, k)
    bias = jnp.asarray(RNG.standard_normal(n).astype(np.float32))
    epi = Epilogue(has_bias=True, activation="gelu")
    out = ops.mte_gemm(a, b, bias=bias, epilogue=epi, format_policy="int8")
    # Bit-exact vs the shared-quantizer oracle (same math, no blocking).
    oracle = ref.mte_gemm(a, b, bias=bias, epilogue=epi,
                          format_policy="int8")
    np.testing.assert_array_equal(out, oracle)
    # Tolerance-bounded vs the fp32 ground truth.
    want = ref.mte_gemm(a, b, bias=bias, epilogue=epi)
    assert _rel(out, want) < 0.05


@pytest.mark.parametrize("m,n,k", SHAPES)
def test_bf16_forward_parity(m, n, k):
    a, b = _mats(m, n, k)
    out = ops.mte_gemm(a, b, format_policy="bf16")
    want = ref.mte_gemm(a, b)
    assert _rel(out, want) < 0.02
    oracle = ref.mte_gemm(a, b, format_policy="bf16")
    np.testing.assert_allclose(out, oracle, rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("m,n,k", SHAPES)
def test_bf16acc_forward_parity(m, n, k):
    a, b = _mats(m, n, k)
    out = ops.mte_gemm(a, b, format_policy="bf16acc")
    want = ref.mte_gemm(a, b)
    assert _rel(out, want) < 0.05  # bf16 accumulation, order-sensitive


def test_all_backends_agree_per_format():
    a, b = _mats(48, 64, 80)
    for fmt in ("bf16", "int8"):
        outs = [dispatch.mte_gemm(a, b, backend=be, format_policy=fmt)
                for be in ("pallas", "xla", "reference")]
        for o in outs[1:]:
            np.testing.assert_allclose(outs[0], o, rtol=1e-5, atol=1e-4)


def test_rigid_baseline_runs_quantized_format():
    a, b = _mats(64, 96, 128)
    out = ops.mte_gemm(a, b, policy="amx", format_policy="int8")
    want = ref.mte_gemm(a, b, format_policy="int8")
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("fmt", ["bf16", "int8"])
def test_grouped_gemm_formats(fmt):
    x = jnp.asarray(RNG.standard_normal((4, 24, 64)).astype(np.float32))
    w = jnp.asarray(RNG.standard_normal((4, 64, 96)).astype(np.float32))
    epi = Epilogue(activation="silu")
    out = ops.grouped_gemm(x, w, epilogue=epi, format_policy=fmt)
    oracle = ref.grouped_gemm(x, w, epilogue=epi, format_policy=fmt)
    np.testing.assert_allclose(out, oracle, rtol=1e-5, atol=1e-4)
    want = ref.grouped_gemm(x, w, epilogue=epi)
    assert _rel(out, want) < 0.05


def test_int8_splitk_route_exists_and_matches():
    """Deep-K decode shapes now get split-K under int8 (int32 partials)."""
    fp = formats.FORMATS["int8"]
    plan = autotune.get_plan(1, 256, 4096, jnp.int8, jnp.int32, fmt="int8")
    assert plan.route == "splitk" and plan.geometry.split_k > 1
    a8 = jnp.asarray(RNG.integers(-64, 64, (1, 4096)), jnp.int8)
    b8 = jnp.asarray(RNG.integers(-64, 64, (4096, 256)), jnp.int8)
    out = autotune.execute_plan(plan, a8, b8)
    want = jnp.asarray(a8, jnp.int32) @ jnp.asarray(b8, jnp.int32)
    np.testing.assert_array_equal(out, want)
    assert fp.quantized


# -- gradients: straight-through estimator ------------------------------------


@pytest.mark.parametrize("fmt", ["bf16", "bf16acc", "int8"])
def test_gradient_parity_ste(fmt):
    """With a linear loss, every format's grads equal the fp32 grads
    exactly — the backward runs on full-precision residuals."""
    a, b = _mats(32, 48, 64)
    bias = jnp.asarray(RNG.standard_normal(48).astype(np.float32))
    ct = jnp.asarray(RNG.standard_normal((32, 48)).astype(np.float32))
    epi = Epilogue(has_bias=True, activation="silu")

    def make_loss(f):
        def loss(a_, b_, bias_):
            out = ops.mte_gemm(a_, b_, bias=bias_, epilogue=epi,
                               format_policy=f)
            return jnp.sum(out * ct)
        return jax.grad(loss, argnums=(0, 1, 2))

    g_fp32 = make_loss("fp32")(a, b, bias)
    g_fmt = make_loss(fmt)(a, b, bias)
    for gf, g32 in zip(g_fmt, g_fp32):
        np.testing.assert_array_equal(gf, g32)


def test_gradient_vs_fp32_oracle_nonlinear_loss():
    """Under a *nonlinear* loss the cotangent depends on the (quantized)
    forward output, so int8-route grads drift from the fp32 oracle's
    grads only by the forward quantization error — bounded at 5%.  (The
    jnp quantized oracle itself differentiates through round(), whose
    a.e.-zero derivative makes it useless as a gradient reference; STE
    is the documented contract instead.)"""
    a, b = _mats(24, 40, 56)

    def k_loss(a_, b_):
        return jnp.sum(ops.mte_gemm(a_, b_, format_policy="int8") ** 2)

    def r32_loss(a_, b_):
        return jnp.sum(ref.mte_gemm(a_, b_) ** 2)

    gk = jax.grad(k_loss, argnums=(0, 1))(a, b)
    g32 = jax.grad(r32_loss, argnums=(0, 1))(a, b)
    for gk_, g32_ in zip(gk, g32):
        assert _rel(gk_, g32_) < 0.05


# -- plan-cache keying --------------------------------------------------------


def test_distinct_formats_distinct_plans_same_format_hits():
    cache = autotune.plan_cache()
    p_bf16 = autotune.get_plan(64, 128, 256, jnp.bfloat16, jnp.bfloat16,
                               fmt="bf16")
    p_acc = autotune.get_plan(64, 128, 256, jnp.bfloat16, jnp.bfloat16,
                              fmt="bf16acc")
    assert p_bf16.signature != p_acc.signature
    assert len(cache) == 2 and cache.stats.misses == 2
    again = autotune.get_plan(64, 128, 256, jnp.bfloat16, jnp.bfloat16,
                              fmt="bf16")
    assert cache.stats.hits == 1 and again is p_bf16


def test_format_inferred_from_dtype_when_unset():
    p = autotune.get_plan(32, 64, 96, jnp.bfloat16, jnp.float32)
    assert p.signature.fmt == "bf16"
    p8 = autotune.get_plan(32, 64, 96, jnp.int8, jnp.int32)
    assert p8.signature.fmt == "int8"


def test_plan_persistence_is_format_keyed(tmp_path):
    autotune.get_plan(16, 256, 512, jnp.float32, fmt="fp32")
    autotune.get_plan(16, 256, 512, jnp.int8, jnp.int32, fmt="int8")
    path = tmp_path / "plans.json"
    autotune.save_plans(str(path))
    doc = json.loads(path.read_text())
    assert doc["version"] == 2
    assert sorted(p["sig"]["fmt"] for p in doc["plans"]) == ["fp32", "int8"]
    autotune.reset_cache()
    assert autotune.load_plans(str(path)) == 2
    cache = autotune.plan_cache()
    autotune.get_plan(16, 256, 512, jnp.int8, jnp.int32, fmt="int8")
    assert cache.stats.hits == 1 and cache.stats.misses == 0


# -- ISA sweep reaches E8 -----------------------------------------------------


def test_isa_sew_sweep_covers_e8():
    sweep = count_sew_sweep(3136, 64, 288)
    assert set(sweep) == {"E8", "E16", "E32"}
    # Narrower SEW ⇒ wider Formula-3 K tile ⇒ fewer retired instructions.
    totals = [sweep[s]["mte32s"].total for s in ("E8", "E16", "E32")]
    assert totals[0] < totals[1] < totals[2]


def test_perfmodel_ranks_narrow_sew_faster():
    us = {}
    for fmt in ("fp32", "bf16", "int8"):
        us[fmt] = dispatch.plan_gemm(1, 4096, 4096,
                                     format_policy=fmt).timing.seconds
    assert us["int8"] < us["bf16"] < us["fp32"]


def test_benchmark_format_modeled_monotone():
    rows = {f: autotune.benchmark_format(1, 1024, 1024, f, measure=False)
            for f in ("fp32", "bf16", "int8")}
    assert (rows["int8"]["modeled_us"] < rows["bf16"]["modeled_us"]
            < rows["fp32"]["modeled_us"])


# -- models consume the policy ------------------------------------------------


def test_dense_layer_honors_format_policy():
    import dataclasses

    from repro.configs import get_config
    from repro.models.layers import dense, init_dense, model_format

    cfg = get_config("gemma_2b").reduced()
    assert cfg.format_policy is None  # reduced() drops the production fmt
    assert get_config("gemma_2b").format_policy == "bf16"
    assert model_format(cfg).name == "fp32"

    p = init_dense(jax.random.PRNGKey(0), 64, 32, bias=True)
    x = jnp.asarray(RNG.standard_normal((4, 8, 64)).astype(np.float32))
    cfg8 = dataclasses.replace(cfg, format_policy="int8",
                               gemm_backend="pallas")
    y8 = dense(x, p, cfg8, activation="gelu")
    y32 = dense(x, p, dataclasses.replace(cfg, gemm_backend="pallas"),
                activation="gelu")
    assert y8.shape == y32.shape and _rel(y8, y32) < 0.06
    # XLA path agrees with the pallas path under the same policy.
    y8_xla = dense(x, p, dataclasses.replace(cfg8, gemm_backend="xla"),
                   activation="gelu")
    np.testing.assert_allclose(y8, y8_xla, rtol=1e-5, atol=1e-4)


def test_configs_carry_format_policies():
    from repro.configs import get_config
    assert get_config("granite_moe_1b").format_policy == "int8"
    assert get_config("qwen15_4b").format_policy == "bf16acc"
    with pytest.raises(AssertionError):
        import dataclasses
        dataclasses.replace(get_config("gemma_2b"), format_policy="fp8")


# -- conv: one grouped launch -------------------------------------------------


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_conv_grouped_launch_matches_lax(backend):
    from repro.core.conv import conv2d_direct

    x = jnp.asarray(RNG.standard_normal((2, 9, 9, 16)).astype(np.float32))
    w = jnp.asarray(RNG.standard_normal((3, 3, 16, 32)).astype(np.float32))
    cb = jnp.asarray(RNG.standard_normal(32).astype(np.float32))
    y = conv2d_direct(x, w, bias=cb, pad=1,
                      epilogue=Epilogue(has_bias=True, activation="relu"),
                      backend=backend)
    want = jax.lax.conv_general_dilated(
        x, w, (1, 1), [(1, 1), (1, 1)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    want = jnp.maximum(want + cb, 0)
    np.testing.assert_allclose(y, want, rtol=2e-4, atol=2e-4)


def test_conv_hits_plan_cache_once_per_shape():
    from repro.core.conv import conv2d_direct

    cache = autotune.plan_cache()
    x = jnp.asarray(RNG.standard_normal((1, 8, 8, 8)).astype(np.float32))
    w = jnp.asarray(RNG.standard_normal((3, 3, 8, 16)).astype(np.float32))
    conv2d_direct(x, w, backend="pallas")
    assert len(cache) == 1 and cache.stats.misses == 1
    conv2d_direct(x, w, backend="pallas")   # same shape: pure hit
    assert cache.stats.misses == 1 and cache.stats.hits >= 1
    conv2d_direct(x, w, backend="pallas", format_policy="int8")
    assert len(cache) == 2                  # new format, new plan


# -- training-side plan persistence -------------------------------------------


def test_plan_snapshot_roundtrip_through_checkpoint(tmp_path):
    from repro.checkpoint.manager import CheckpointManager
    from repro.training.trainer import (plan_cache_snapshot,
                                        restore_plan_cache)

    assert plan_cache_snapshot() is None  # empty cache → nothing to save
    autotune.get_plan(8, 128, 256, jnp.float32, fmt="fp32")
    autotune.get_plan(8, 128, 256, jnp.int8, jnp.int32, fmt="int8")
    snap = plan_cache_snapshot()
    assert snap and len(snap["plans"]) == 2

    mgr = CheckpointManager(str(tmp_path / "ckpt"))
    params = {"w": jnp.ones((2, 2))}
    opt = {"m": jnp.zeros((2, 2))}
    mgr.save(3, params, opt, extra={"data": {"pos": 1}}, gemm_plans=snap)

    autotune.reset_cache()
    assert len(autotune.plan_cache()) == 0
    assert mgr.restore_plans() == 2
    cache = autotune.plan_cache()
    autotune.get_plan(8, 128, 256, jnp.int8, jnp.int32, fmt="int8")
    assert cache.stats.hits == 1 and cache.stats.misses == 0

    # restore() still hands the manifest back with the plans attached.
    _, _, manifest = mgr.restore(None, (params, opt))
    assert manifest["gemm_plans"]["version"] == 2
    # corrupt/mismatched snapshots degrade to a cold start, not a crash
    assert restore_plan_cache({"version": 99}) == 0
    assert restore_plan_cache(None) == 0


# -- serving: per-request precision + format-keyed warm start -----------------


def test_serving_per_request_format(tmp_path):
    import dataclasses

    from repro.configs import get_config
    from repro.models import model as model_lib
    from repro.serving.engine import Request, ServingEngine

    cfg = dataclasses.replace(get_config("gemma_2b").reduced(),
                              n_layers=2, vocab=128)
    params = model_lib.init_params(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(params, cfg, slots=2, cache_len=64, prefill_len=16,
                        format_policy="bf16")
    assert eng.cfg.format_policy == "bf16"
    prompt = np.asarray(RNG.integers(0, 128, 12), np.int32)
    eng.submit(Request(rid=0, prompt=prompt, max_tokens=4))
    eng.submit(Request(rid=1, prompt=prompt, max_tokens=4,
                       format_policy="int8"))
    out = eng.run(max_steps=20)
    assert set(out) == {0, 1}
    assert all(len(v) >= 4 for v in out.values())
    # One jitted prefill per distinct format policy.
    assert set(eng._prefill_fns) == {None, "int8"}
    # Naming the engine's own default shares its compilation...
    eng.submit(Request(rid=2, prompt=prompt, max_tokens=2,
                       format_policy="bf16"))
    eng.run(max_steps=10)
    assert set(eng._prefill_fns) == {None, "int8"}
    # ...and a typo'd policy fails at submit, not inside the batch loop.
    with pytest.raises(ValueError):
        eng.submit(Request(rid=3, prompt=prompt, format_policy="fp8"))


def _prefill_logits(eng, fmt, tokens):
    """Logits of the engine's first prefill chunk under policy ``fmt``.
    The chunk program takes the engine's cache donated, so the engine
    keeps the cache it returns, as ``ServingEngine`` does."""
    batch = eng._make_batch(tokens[None], table=eng.sched.table_row(0)[None],
                            slot=0)
    logits, eng.cache = eng._chunk_fn(fmt, 0)(eng.params, batch, eng.cache)
    return np.asarray(logits, np.float32)


def test_serving_fp32_request_reads_the_weights_as_given():
    """An fp32 request on a bf16 engine computes with the fp32 weights it
    was given: its prefill logits equal those of an fp32 engine."""
    import dataclasses

    from repro.configs import get_config
    from repro.models import model as model_lib
    from repro.serving.engine import ServingEngine

    cfg = dataclasses.replace(get_config("gemma_2b").reduced(),
                              n_layers=2, vocab=128, compute_dtype="bfloat16")
    params = model_lib.init_params(jax.random.PRNGKey(0), cfg)
    kw = dict(slots=1, cache_len=64, prefill_len=16)
    bf16 = ServingEngine(params, cfg, format_policy="bf16", **kw)
    fp32 = ServingEngine(params, cfg, format_policy="fp32", **kw)
    tokens = np.asarray(RNG.integers(0, 128, 16), np.int32)
    got = _prefill_logits(bf16, "fp32", tokens)
    np.testing.assert_array_equal(got, _prefill_logits(fp32, None, tokens))
    assert not np.array_equal(got, _prefill_logits(bf16, None, tokens))


@pytest.mark.parametrize("others, narrowed", [
    ((), True), (("bf16",), True), (("fp32",), False), (("int8",), False)])
def test_serving_params_narrow_only_for_every_reader(others, narrowed):
    """Weights go to bf16 only when every policy that reads them rounds
    them to bf16 first; a narrowed tree serves bf16 exactly as fp32."""
    import dataclasses

    from repro.configs import get_config
    from repro.models import model as model_lib
    from repro.serving.engine import init_serving_params, serving_params

    cfg = dataclasses.replace(get_config("gemma_2b").reduced(),
                              n_layers=2, vocab=128, format_policy="bf16",
                              compute_dtype="bfloat16")
    readers = [dataclasses.replace(cfg, format_policy=f) for f in others]
    key = jax.random.PRNGKey(0)
    params = model_lib.init_params(key, cfg)
    held = serving_params(params, cfg, *readers)
    want = jnp.bfloat16 if narrowed else jnp.float32
    assert held["embedding"]["table"].dtype == want
    assert held["groups"][0]["ffn"]["up"]["w"].dtype == want
    assert held["final_norm"]["scale"].dtype == jnp.float32
    assert jax.tree.map(lambda x: x.dtype,
                        init_serving_params(key, cfg, *readers)) == \
        jax.tree.map(lambda x: x.dtype, held)
    if narrowed:
        tokens = jnp.asarray(RNG.integers(0, 128, (1, 16)), jnp.int32)
        run = jax.jit(lambda p: model_lib.prefill(p, {"tokens": tokens},
                                                  cfg)[0])
        np.testing.assert_array_equal(np.asarray(run(held), np.float32),
                                      np.asarray(run(params), np.float32))
