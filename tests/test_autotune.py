"""Plan-cache behaviour: memoization, routing, persistence (ISSUE 1)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import autotune
from repro.core import dispatch
from repro.core import geometry
from repro.core.epilogue import Epilogue
from repro.kernels import ops, ref

RNG = np.random.default_rng(7)


@pytest.fixture(autouse=True)
def fresh_cache():
    autotune.reset_cache()
    yield
    autotune.reset_cache()


def _mats(m, n, k, dtype=np.float32):
    a = RNG.standard_normal((m, k)).astype(dtype)
    b = RNG.standard_normal((k, n)).astype(dtype)
    return jnp.asarray(a), jnp.asarray(b)


# -- memoization --------------------------------------------------------------


def test_same_signature_hits_cache_and_solver_runs_once(monkeypatch):
    calls = {"n": 0}
    real = geometry.solve_block_geometry

    def counting(*a, **kw):
        calls["n"] += 1
        return real(*a, **kw)

    monkeypatch.setattr(autotune, "solve_block_geometry", counting)
    for _ in range(5):
        autotune.get_plan(256, 512, 1024, jnp.float32,
                          epilogue=Epilogue(activation="gelu"))
    assert calls["n"] == 1
    st = autotune.cache_stats()
    assert st.misses == 1 and st.hits == 4 and st.solver_calls == 1


def test_dispatch_repeat_calls_hit_cache():
    a, b = _mats(64, 128, 96)
    for _ in range(3):
        dispatch.mte_gemm(a, b, backend="pallas")
    st = autotune.cache_stats()
    # one miss (and one solve) for the signature no matter how many calls
    assert st.solver_calls == st.misses == 1
    assert st.hits >= 2


def test_measure_upgrades_analytic_hit():
    """measure=True on a signature first planned analytically must refine
    it, not silently return the unmeasured plan."""
    p1 = autotune.get_plan(8, 256, 512, jnp.float32)
    assert p1.measured_s is None
    p2 = autotune.get_plan(8, 256, 512, jnp.float32, measure=True)
    assert p2.source == "measured" and p2.measured_s is not None
    # ...and the refined plan is what the cache now serves.
    p3 = autotune.get_plan(8, 256, 512, jnp.float32)
    assert p3 is p2


def test_distinct_epilogues_and_dtypes_get_distinct_plans():
    autotune.get_plan(64, 64, 64, jnp.float32, epilogue=Epilogue())
    autotune.get_plan(64, 64, 64, jnp.float32,
                      epilogue=Epilogue(activation="relu"))
    autotune.get_plan(64, 64, 64, jnp.bfloat16, jnp.float32,
                      epilogue=Epilogue())
    st = autotune.cache_stats()
    assert st.misses == 3 and len(autotune.plan_cache()) == 3


def test_lru_eviction():
    cache = autotune.reset_cache(maxsize=2)
    for n in (128, 256, 384):
        autotune.get_plan(64, n, 64, jnp.float32)
    assert len(cache) == 2
    # oldest signature re-solves after eviction
    autotune.get_plan(64, 128, 64, jnp.float32)
    assert cache.stats.misses == 4


# -- routing ------------------------------------------------------------------


def test_tall_skinny_routes_to_splitk():
    """Acceptance: M <= 32 with K >= 8N must take the split-K route."""
    plan = autotune.get_plan(16, 256, 4096, jnp.float32)
    assert plan.route == "splitk" and plan.n_split > 1
    assert plan.predicted_s > 0


def test_dispatch_launches_splitk_kernel(monkeypatch):
    """dispatch.mte_gemm(backend='pallas') must actually launch the
    split-K kernel for the decode shape, and match the oracle."""
    import repro.kernels.autodiff as ad
    from repro.kernels import splitk_gemm
    launches = {"n": 0}
    real = splitk_gemm.mte_gemm_splitk_pallas

    def counting(*a, **kw):
        launches["n"] += 1
        return real(*a, **kw)

    monkeypatch.setattr(splitk_gemm, "mte_gemm_splitk_pallas", counting)
    a, b = _mats(16, 256, 4096)
    out = dispatch.mte_gemm(a, b, backend="pallas")
    assert launches["n"] == 1
    np.testing.assert_allclose(out, ref.mte_gemm(a, b), rtol=3e-4,
                               atol=3e-4)


def test_large_square_does_not_split():
    plan = autotune.get_plan(1024, 1024, 512, jnp.float32)
    assert plan.route == "mte" and plan.n_split == 1


def test_amx_policy_is_rigid_and_unsearched():
    plan = autotune.get_plan(16, 256, 4096, jnp.float32, policy="amx")
    assert plan.route == "rigid"
    assert (plan.geometry.bm, plan.geometry.bn) == (128, 128)


def test_grouped_signature_routes_grouped():
    plan = autotune.get_plan(40, 96, 64, jnp.float32, group=4)
    assert plan.route == "grouped"


def test_autotuned_never_predicted_slower_than_analytic():
    """The analytic plan is always in the candidate set, so the winner's
    predicted cost is <= the analytic plan's predicted cost."""
    shapes = [(1, 4096, 4096), (16, 256, 4096), (512, 512, 512),
              (33, 257, 65), (8, 2048, 8)]
    for m, n, k in shapes:
        sig = autotune.GemmSignature.make(m, n, k, "float32", "float32")
        cands = autotune.enumerate_candidates(sig)
        analytic_s = autotune.score_geometry(sig, cands[0])
        plan = autotune.get_plan(m, n, k, jnp.float32)
        assert plan.predicted_s <= analytic_s * (1 + 1e-9), (m, n, k)


# -- persistence --------------------------------------------------------------


def test_json_roundtrip_warm_start(tmp_path):
    path = str(tmp_path / "plans.json")
    p1 = autotune.get_plan(16, 256, 4096, jnp.float32,
                           epilogue=Epilogue(has_bias=True))
    p2 = autotune.get_plan(64, 64, 64, jnp.bfloat16, jnp.float32)
    autotune.save_plans(path)

    autotune.reset_cache()
    assert autotune.load_plans(path) == 2
    w1 = autotune.get_plan(16, 256, 4096, jnp.float32,
                           epilogue=Epilogue(has_bias=True))
    w2 = autotune.get_plan(64, 64, 64, jnp.bfloat16, jnp.float32)
    st = autotune.cache_stats()
    assert st.solver_calls == 0 and st.hits == 2  # warm start: no re-solve
    assert w1.source == "warmstart" and w2.source == "warmstart"
    assert w1.geometry == p1.geometry and w1.route == p1.route
    assert w2.geometry == p2.geometry


def test_serving_engine_warm_start(tmp_path):
    path = str(tmp_path / "serving_plans.json")
    autotune.get_plan(1, 4096, 4096, jnp.float32)
    autotune.save_plans(path)
    autotune.reset_cache()

    import dataclasses as dc
    from repro.configs import get_config
    from repro.serving.engine import ServingEngine
    import jax
    cfg = get_config("gemma_2b").reduced()
    cfg = dc.replace(cfg, n_layers=1, d_model=32, d_ff=64, vocab=64,
                     n_heads=2, n_kv_heads=1, head_dim=16)
    from repro.models import model as model_lib
    params = model_lib.init_params(jax.random.PRNGKey(0), cfg)
    ServingEngine(params, cfg, slots=1, cache_len=32, prefill_len=8,
                  plan_cache_path=path)
    assert len(autotune.plan_cache()) == 1  # warm-started at construction


def test_measured_refinement_picks_a_candidate():
    plan = autotune.get_plan(8, 256, 512, jnp.float32, measure=True)
    assert plan.source == "measured" and plan.measured_s is not None
    assert autotune.cache_stats().measured >= 2


def test_measured_refinement_propagates_launch_errors(monkeypatch):
    """A candidate that fails to launch (a kernel the compiler refuses)
    must not silently lose the search to another route."""
    def refused(plan, iters=3, interpret=None):
        if plan.route != "xla":
            raise ValueError("block shape not aligned to the tiling")
        return 1e-3

    monkeypatch.setattr(autotune, "measure_plan", refused)
    with pytest.raises(ValueError, match="aligned"):
        autotune.get_plan(8, 256, 512, jnp.float32, measure=True)


def test_capability_mismatch_is_decided_before_launch(monkeypatch):
    """The MTE kernel fuses row bias only: a column-bias signature skips
    that route without launching it and counts it in measure_failed."""
    launched = []

    def timed(plan, iters=3, interpret=None):
        launched.append(plan.route)
        return 1e-3

    monkeypatch.setattr(autotune, "measure_plan", timed)
    epi = Epilogue(has_bias=True, bias_axis="col")
    plan = autotune.get_plan(256, 256, 256, jnp.float32, epilogue=epi,
                             measure=True)
    assert "mte" not in launched and plan.route != "mte"
    assert autotune.cache_stats().measure_failed >= 1
