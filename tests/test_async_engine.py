"""Async pipelined engine step: bit-identity, flush boundaries, buffer
donation, work conservation, the trace-overlap witness, and the graph
weight-prefetch plan.

The contract under test: ``async_steps=True`` changes *when* sampled
tokens reach the host (delivery lags launch by up to one step), never
*which* tokens any request receives — both modes run the identical
jitted decode+sample program, so greedy outputs are bit-identical by
construction, and these tests pin that construction against drift.
"""
import dataclasses
import gc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import model as model_lib
from repro.serving import Request, ServingEngine
from repro.serving.resilience import Fault, FaultInjector
from repro.telemetry import tracing
from repro.telemetry.export import health, validate_health


def _cfg():
    cfg = get_config("gemma_2b").reduced()
    return dataclasses.replace(cfg, n_layers=2, d_model=64, d_ff=128,
                               vocab=128, n_heads=2, n_kv_heads=1,
                               head_dim=32)


@pytest.fixture(scope="module")
def setup():
    cfg = _cfg()
    params = model_lib.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _workload(cfg, n_req=5, lo=10, hi=16, base_tokens=6):
    """Staggered prompts/budgets: multi-chunk prefills and unequal
    finish steps, so admissions and continuing chunks land while a
    decode is in flight (the depth-2 window)."""
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab, size=int(rng.integers(lo, hi)),
                            dtype=np.int32) for _ in range(n_req)]
    budgets = [base_tokens + (i % 3) * 2 for i in range(n_req)]
    return prompts, budgets


def _serve(params, cfg, prompts, budgets, *, async_steps, **kw):
    eng = ServingEngine(params, cfg, slots=2, cache_len=64,
                        prefill_len=16, page_size=8, prefill_chunk=8,
                        async_steps=async_steps, **kw)
    for rid, p in enumerate(prompts):
        eng.submit(Request(rid=rid, prompt=p, max_tokens=budgets[rid]))
    out = eng.run()
    return {rid: tuple(r) for rid, r in out.items()}, eng


# -- greedy bit-identity ------------------------------------------------------


@pytest.mark.parametrize("arch,spec_k", [("gemma_2b", 0),
                                         ("gemma_2b", 2),
                                         ("recurrentgemma_9b", 0)])
def test_greedy_bit_identity_async_on_off(arch, spec_k):
    """Same workload, async on vs off: identical token streams — across
    a pure-attention arch, a hybrid recurrent arch (per-slot carried
    state rides ``row_valid`` through the pipelined decode), and with
    speculation (which flushes to its own synchronous verify step)."""
    if arch == "gemma_2b":
        cfg = _cfg()
    else:
        cfg = get_config(arch).reduced()
    params = model_lib.init_params(jax.random.PRNGKey(0), cfg)
    prompts, budgets = _workload(cfg, n_req=4)
    sync_toks, _ = _serve(params, cfg, prompts, budgets,
                          async_steps=False, spec_k=spec_k)
    async_toks, eng = _serve(params, cfg, prompts, budgets,
                             async_steps=True, spec_k=spec_k)
    assert async_toks == sync_toks
    assert all(len(t) > 0 for t in async_toks.values())
    if spec_k == 0:
        assert eng.metrics()["delivery_lag_mean"] > 0.0


def test_greedy_bit_identity_under_mid_run_eviction(setup):
    """A pool small enough to force preemption mid-run: the eviction
    boundary flushes the pipeline before the victim's host-visible
    output is requeued, so replay produces the same tokens either way."""
    cfg, params = setup
    prompts, budgets = _workload(cfg, n_req=3, base_tokens=10)
    sync_toks, sync_eng = _serve(params, cfg, prompts, budgets,
                                 async_steps=False, num_pages=7)
    async_toks, async_eng = _serve(params, cfg, prompts, budgets,
                                   async_steps=True, num_pages=7)
    assert async_toks == sync_toks
    # the scenario only bites if someone actually got preempted
    assert sync_eng.metrics()["preemptions"] >= 1
    assert async_eng.metrics()["preemptions"] >= 1


# -- flush boundaries and pipeline depth --------------------------------------


def test_snapshot_flushes_pipeline_and_health_reports_staleness(setup):
    """Mid-flight: ``steps_in_flight`` > 0, the health snapshot carries
    the staleness note (and validates); ``snapshot()`` is a flush
    boundary, so afterwards nothing is in flight."""
    cfg, params = setup
    eng = ServingEngine(params, cfg, slots=2, cache_len=64,
                        prefill_len=16, page_size=8, async_steps=True)
    eng.submit(Request(rid=0, prompt=np.arange(8, dtype=np.int32),
                       max_tokens=12))
    eng._admit()
    for _ in range(3):
        eng.step()
    assert eng.steps_in_flight >= 1
    doc = health(engine=eng)
    assert validate_health(doc) == []
    assert doc["scheduler"]["steps_in_flight"] >= 1
    assert "lag" in doc["scheduler"]["staleness"]
    eng.snapshot()
    assert eng.steps_in_flight == 0
    out = eng.run()
    assert len(out[0]) == 12


def test_pipeline_reaches_depth_two(setup):
    cfg, params = setup
    prompts, budgets = _workload(cfg)
    _, eng = _serve(params, cfg, prompts, budgets, async_steps=True)
    assert eng.steps_in_flight_max >= 2
    assert eng.steps_in_flight == 0      # run() end is a flush boundary
    _, sync_eng = _serve(params, cfg, prompts, budgets, async_steps=False)
    assert sync_eng.steps_in_flight_max <= 1


def test_fault_injection_forces_synchronous_depth(setup):
    """An armed injector pins the effective depth to 1: poison/sample
    overrides are host-side and must fire in the decode's own step."""
    cfg, params = setup
    prompts, budgets = _workload(cfg, n_req=3)
    inj = FaultInjector([Fault("poison_logits", rid=1, step=4)])
    toks, eng = _serve(params, cfg, prompts, budgets,
                       async_steps=True, fault=inj)
    assert eng.steps_in_flight_max <= 1
    assert all(len(t) > 0 for rid, t in toks.items() if rid != 1)


def test_work_conservation_vs_sync(setup):
    """Async must not burn steps: delivered finishes are re-admitted in
    the same step (second admission pass), so the step-count overhead
    is bounded by trailing drain-only steps — never bubble decodes."""
    cfg, params = setup
    prompts, budgets = _workload(cfg)
    _, sync_eng = _serve(params, cfg, prompts, budgets, async_steps=False)
    _, async_eng = _serve(params, cfg, prompts, budgets, async_steps=True)
    assert async_eng.step_idx - sync_eng.step_idx <= 3
    assert async_eng.metrics()["delivery_lag_mean"] == pytest.approx(1.0)
    assert sync_eng.metrics()["delivery_lag_mean"] == 0.0


# -- donation -----------------------------------------------------------------


def test_decode_steps_do_not_grow_live_buffers(setup):
    """The decode program donates the KV cache and carries the token
    array on device: consecutive steps must not accumulate live device
    buffers (each step's outputs replace the previous step's)."""
    if not hasattr(jax, "live_arrays"):
        pytest.skip("jax.live_arrays not available")
    cfg, params = setup
    eng = ServingEngine(params, cfg, slots=2, cache_len=64,
                        prefill_len=16, page_size=8, async_steps=True)
    eng.submit(Request(rid=0, prompt=np.arange(8, dtype=np.int32),
                       max_tokens=40))
    eng._admit()
    for _ in range(4):   # warm: compile, seed, reach steady decode state
        eng.step()
    gc.collect()
    counts = []
    for _ in range(4):
        eng.step()
        gc.collect()
        counts.append(len(jax.live_arrays()))
    assert max(counts) == min(counts), counts


# -- trace witness ------------------------------------------------------------


def test_trace_decode_overlaps_next_step_host_work(setup):
    """The async decode span stays open until delivery, so it must
    overlap the NEXT step's host spans (prefill chunks, delivery
    sampling); the synchronous trace must show no decode x
    prefill_chunk overlap — pipelining, not span bookkeeping."""
    cfg, params = setup
    prompts, budgets = _workload(cfg)

    def traced(async_steps):
        tr = tracing.install(tracing.Tracer())
        try:
            _serve(params, cfg, prompts, budgets, async_steps=async_steps)
        finally:
            tracing.uninstall()
        return tr.to_json()

    doc = traced(True)
    assert tracing.span_overlaps(doc, "decode", "prefill_chunk")
    assert tracing.span_overlaps(doc, "decode", "sample")
    assert tracing.validate_trace(
        doc, require_names=("decode", "prefill_chunk", "admit"),
        require_overlap=(("decode", "prefill_chunk"),
                         ("decode", "sample"))) == []
    sync_doc = traced(False)
    assert not tracing.span_overlaps(sync_doc, "decode", "prefill_chunk")


def test_profile_holds_the_engine_host_spans_and_no_decode(setup,
                                                          tmp_path):
    """Under a profiler session with a tracer installed, the step's host
    phases are annotations on the profiler's clock; the in-flight
    ``decode`` interval (device residency, not host work) stays in the
    JSON only, and every jitted serving program has a ``serve_`` name."""
    cfg, params = setup
    prompts, budgets = _workload(cfg, n_req=3)
    eng = ServingEngine(params, cfg, slots=2, cache_len=64,
                        prefill_len=16, page_size=8, prefill_chunk=8)
    for rid, p in enumerate(prompts):
        eng.submit(Request(rid=rid, prompt=p, max_tokens=budgets[rid]))
    tr = tracing.install(tracing.Tracer())
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.run()
    finally:
        jax.profiler.stop_trace()
        tracing.uninstall()
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    data = jax.profiler.ProfileData.from_file(str(path))
    names = {ev.name for plane in data.planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events}
    assert {"step", "admit", "prefill_chunk", "device_wait", "sample",
            "evict", "decode_launch", "observe"} <= names
    assert "decode" not in names
    assert "decode" in {e["name"] for e in tr.events}
    programs = (eng._decode_step, eng._scatter_tok, eng._chunk_fn(None, 1))
    assert [p.__name__ for p in programs] == [
        "serve_decode", "serve_scatter_token", "serve_prefill_chunk"]


def test_span_overlaps_and_validate_trace_unit():
    def ev(name, ts, dur):
        return {"name": name, "ph": "X", "ts": ts, "dur": dur,
                "pid": 1, "tid": 1}

    doc = {"traceEvents": [ev("a", 0, 10), ev("b", 5, 10),
                           ev("c", 20, 5)]}
    assert tracing.span_overlaps(doc, "a", "b")
    assert not tracing.span_overlaps(doc, "a", "c")
    # touching endpoints are NOT overlap (strict inequalities)
    doc2 = {"traceEvents": [ev("a", 0, 10), ev("b", 10, 10)]}
    assert not tracing.span_overlaps(doc2, "a", "b")
    errs = tracing.validate_trace(doc, require_overlap=(("a", "c"),))
    assert any("'a' x 'c'" in e for e in errs)
    assert tracing.validate_trace(doc, require_overlap=(("a", "b"),)) == []


# -- graph weight prefetch ----------------------------------------------------


def test_graph_emits_weight_prefetch_plan():
    """Cross-layer double-buffering: a two-GEMM chain prefetches the
    second layer's (graph-input) weights during the first's compute.
    ``modeled_s`` stays the no-overlap figure — baselines and fusion
    scoring are unchanged; the saving is annotated separately."""
    from repro.graph import GraphBuilder, compile_graph

    rng = np.random.default_rng(0)

    def build():
        b = GraphBuilder()
        x = b.input((8, 32), "float32")
        w1 = b.input((32, 32), "float32")
        w2 = b.input((32, 24), "float32")
        b.output(b.gemm(b.gemm(x, w1, fmt="fp32"), w2, fmt="fp32"))
        return b.build()

    args = (jnp.asarray(rng.standard_normal((8, 32)), jnp.float32),
            jnp.asarray(rng.standard_normal((32, 32)), jnp.float32),
            jnp.asarray(rng.standard_normal((32, 24)), jnp.float32))
    prog = compile_graph(build(), fuse=False, prefetch=True)
    assert prog.prefetch and prog.prefetch_saved_s > 0.0
    assert "prefetch" in prog.describe()
    off = compile_graph(build(), fuse=False, prefetch=False)
    assert off.prefetch == {} and off.prefetch_saved_s == 0.0
    assert prog.modeled_s == off.modeled_s
    np.testing.assert_array_equal(np.asarray(prog(*args)),
                                  np.asarray(off(*args)))
