"""repro.telemetry: metrics-registry semantics, span tracer + trace-JSON
schema, per-GEMM dispatch accounting exactness (one record per compiled
dispatch, grouped siblings = ONE record), and the observation-changes-
nothing contract — greedy serving outputs are bit-identical with
telemetry on vs off."""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import autotune, dispatch
from repro.core import formats as formats_lib
from repro.graph import GraphBuilder, compile_graph
from repro.graph import fuse as fuse_mod
from repro.graph import ir as ir_mod
from repro.graph import schedule as sched_mod
from repro.kernels import ops
from repro.models import model as model_lib
from repro.serving.engine import Request, ServingEngine
from repro.serving.kv_cache import KVPagePool
from repro.serving.resilience import Fault, FaultInjector
from repro.telemetry import gemm_account, tracing
from repro.telemetry.registry import (Histogram, MetricsRegistry, publish,
                                      registry, reset_registry)

RNG = np.random.default_rng(11)


@pytest.fixture(autouse=True)
def clean_telemetry():
    """Telemetry is process-global state: every test starts and ends
    with nothing installed and empty caches/registry."""
    autotune.reset_cache()
    sched_mod.reset_programs()
    reset_registry()
    tracing.uninstall()
    gemm_account.uninstall()
    yield
    tracing.uninstall()
    gemm_account.uninstall()
    autotune.reset_cache()
    sched_mod.reset_programs()
    reset_registry()


def _arr(*shape):
    return jnp.asarray(RNG.standard_normal(shape).astype(np.float32))


def _cfg():
    cfg = get_config("gemma_2b").reduced()
    return dataclasses.replace(cfg, n_layers=2, d_model=64, d_ff=128,
                               vocab=128, n_heads=2, n_kv_heads=1,
                               head_dim=32)


# -- metrics registry ---------------------------------------------------------


def test_counter_monotonic_and_gauge():
    reg = MetricsRegistry()
    c = reg.counter("a.b_total")
    c.inc()
    c.inc(3)
    assert c.value == 4
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("a.g")
    g.set(2.5)
    g.set(1.5)
    assert g.value == 1.5


def test_histogram_buckets_mean_percentile():
    h = Histogram("lat_s", edges=(0.001, 0.01, 0.1))
    for v in (0.0005, 0.005, 0.05, 0.5):
        h.observe(v)
    assert h.count == 4
    assert h.mean == pytest.approx((0.0005 + 0.005 + 0.05 + 0.5) / 4)
    # cumulative export: one sample per bucket, +Inf carries the total
    assert h.bucket_counts() == [(0.001, 1), (0.01, 2), (0.1, 3),
                                 (float("inf"), 4)]
    assert h.percentile(0) == 0.0005
    assert h.percentile(100) == 0.5
    assert h.percentile(50) in (0.005, 0.05)
    # unsorted observation order still yields exact percentiles
    h.observe(0.0001)
    assert h.percentile(0) == 0.0001
    with pytest.raises(ValueError):
        Histogram("bad", edges=(0.1, 0.01))


def test_registry_one_type_per_name_and_reset():
    reg = MetricsRegistry()
    reg.counter("x.n")
    assert reg.counter("x.n") is reg.get("x.n")   # idempotent handle
    with pytest.raises(TypeError):
        reg.histogram("x.n")
    reg.histogram("x.h").observe(0.2)
    d = reg.as_dict()
    assert d["x.n"] == 0.0
    assert d["x.h"]["count"] == 1
    assert reg.names() == ["x.h", "x.n"]
    reg.reset()
    assert reg.names() == []


def test_publish_mirrors_numbers_skips_rest():
    publish("sub", {"a": 3, "b": 2.5, "fmt": "int8pt", "flag": True})
    reg = registry()
    assert reg.get("sub.a").value == 3
    assert reg.get("sub.b").value == 2.5
    assert reg.get("sub.fmt") is None       # strings skipped
    assert reg.get("sub.flag") is None      # bools skipped (not numbers)


# -- span tracer + trace-event JSON -------------------------------------------


def test_noop_tracer_is_allocation_free_singleton():
    assert tracing.active() is None
    assert tracing.current() is tracing.NOOP
    # ONE reusable span object — the hot-loop zero-overhead contract
    assert tracing.NOOP.span("a") is tracing.NOOP.span("b")
    with tracing.NOOP.span("decode"):
        pass
    assert tracing.NOOP.instant("x", args={"k": 1}) is None


def test_noop_spans_allocate_nothing_and_never_touch_the_profiler(
        monkeypatch):
    """With nothing installed, every engine span is the one prebuilt
    no-op object, and no profiler annotation is made."""
    def refuse(name):
        raise AssertionError(f"NOOP opened a profiler annotation {name!r}")
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    names = ("step", "admit", "prefill_chunk", "device_wait", "sample",
             "evict", "decode_launch", "observe")
    seen = set()
    for name in names:
        with tracing.current().span(name) as sp:
            seen.add(id(sp))
    assert seen == {id(tracing.NOOP.span("x"))}


def _host_event_names(log_dir):
    (path,) = log_dir.glob("plugins/profile/*/*.xplane.pb")
    data = jax.profiler.ProfileData.from_file(str(path))
    return {ev.name for plane in data.planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events}


def test_tracer_spans_are_profiler_annotations(tmp_path):
    """An installed tracer's spans land in a profiler session's trace
    under their own names; ``annotate=False`` keeps one in the JSON
    only."""
    tr = tracing.install(tracing.Tracer())
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracing.current().span("phase.outer"):
            with tracing.current().span("phase.inner"):
                pass
        with tr.span("phase.on_device", annotate=False):
            pass
    finally:
        jax.profiler.stop_trace()
        tracing.uninstall()
    names = _host_event_names(tmp_path)
    assert {"phase.outer", "phase.inner"} <= names
    assert "phase.on_device" not in names
    assert [e["name"] for e in tr.events] == [
        "phase.inner", "phase.outer", "phase.on_device"]


def test_span_nesting_and_schema(tmp_path):
    t = [0.0]

    def clock():
        t[0] += 0.001   # 1ms per read
        return t[0]

    tr = tracing.Tracer(clock=clock)
    tracing.install(tr)
    assert tracing.current() is tr
    with tracing.current().span("parent"):
        with tracing.current().span("child", args={"slot": 3}):
            pass
        tr.instant("request.first_token", args={"rid": 0})
    tracing.uninstall()
    assert tracing.current() is tracing.NOOP

    by_name = {e["name"]: e for e in tr.events}
    child, parent = by_name["child"], by_name["parent"]
    # children exit first (events append on exit); intervals nest
    assert tr.events[0]["name"] == "child"
    assert parent["ts"] <= child["ts"]
    assert child["ts"] + child["dur"] <= parent["ts"] + parent["dur"]
    assert child["args"] == {"slot": 3}
    inst = by_name["request.first_token"]
    assert inst["ph"] == "i" and inst["s"] == "g"
    assert all(isinstance(e["ts"], int) for e in tr.events)

    doc = tr.to_json()
    assert doc["displayTimeUnit"] == "ms"
    assert tracing.validate_trace(doc) == []
    path = tmp_path / "t.trace.json"
    tr.export(str(path))
    assert tracing.validate_trace_file(str(path)) == []
    assert json.load(open(path))["traceEvents"] == tr.events


def test_validate_trace_rejects_bad_documents(tmp_path):
    assert tracing.validate_trace([]) != []
    assert tracing.validate_trace({}) != []
    assert tracing.validate_trace({"traceEvents": []}) != []   # empty
    bad = {"traceEvents": [{"name": "x", "ph": "X", "ts": 1.5,
                            "pid": 1, "tid": 1}]}
    errs = tracing.validate_trace(bad)
    assert any("dur" in e for e in errs)
    assert any("integer" in e for e in errs)
    assert tracing.validate_trace_file(str(tmp_path / "absent.json")) != []


def test_trace_to_exports_even_on_error(tmp_path):
    path = tmp_path / "run.trace.json"
    with pytest.raises(RuntimeError):
        with tracing.trace_to(str(path)) as tr:
            assert tracing.current() is tr
            with tr.span("phase"):
                pass
            raise RuntimeError("boom")
    assert tracing.active() is None
    assert tracing.validate_trace_file(str(path)) == []


# -- per-GEMM dispatch accounting ---------------------------------------------


def test_shape_class_families():
    assert gemm_account.shape_class(1, 2048, 2048) == "tall_skinny"
    assert gemm_account.shape_class(2048, 16, 2048) == "tall_skinny"
    assert gemm_account.shape_class(8, 8, 8) == "small"
    assert gemm_account.shape_class(256, 256, 256) == "square"
    assert gemm_account.shape_class(64, 8192, 64) == "rect"


def test_pallas_gemm_one_record_with_plan_provenance():
    a, b = _arr(8, 64), _arr(64, 48)
    with gemm_account.account_gemms() as acct:
        ops.mte_gemm(a, b, interpret=True)
        ops.mte_gemm(a, b, interpret=True)
    assert len(acct.records) == 2
    first, second = acct.records
    assert (first.m, first.n, first.k) == (8, 48, 64)
    assert first.backend == "pallas"
    # plan join: a fresh cache grants the plan, the re-dispatch hits it
    assert first.plan_source in ("analytic", "measured", "warmstart")
    assert second.plan_source == "cache-hit"


def test_dispatch_xla_gemm_exactly_one_record():
    """dispatch.mte_gemm records itself and suppresses the inner
    formats.xla_gemm fallback — one dispatch, one record, never two."""
    a, b = _arr(4, 64), _arr(64, 96)
    with gemm_account.account_gemms() as acct:
        dispatch.mte_gemm(a, b, backend="xla")
    assert len(acct.records) == 1
    (r,) = acct.records
    assert r.backend == "xla" and r.policy == "mte"
    assert r.shape_class == "tall_skinny"
    # the XLA backend executes one fused dot without consulting the
    # planner — its records carry no plan grant, by design
    assert r.plan_source == "unplanned"


def test_formats_fallback_records_unplanned_and_suppressible():
    fmt = formats_lib.FORMATS["fp32"]
    a, b = _arr(4, 64), _arr(64, 32)
    with gemm_account.account_gemms() as acct:
        formats_lib.xla_gemm(a, b, fmt)
        with gemm_account.suppress():
            formats_lib.xla_gemm(a, b, fmt)     # hidden: inner compute
    assert len(acct.records) == 1
    (r,) = acct.records
    assert r.policy == "xla" and r.plan_source == "unplanned"
    assert gemm_account.active() is None        # context restored


def test_grouped_siblings_are_one_record():
    """Three sibling GEMMs sharing a left operand, group-fused: the
    compiled program dispatches ONE grouped launch and the accountant
    sees ONE record with group=3 — not three."""
    m, d, n = 8, 64, 48
    b = GraphBuilder()
    x = b.input((m, d), "float32")
    ws = [b.input((d, n), "float32") for _ in range(3)]
    b.output(*(b.gemm(x, w, fmt="fp32") for w in ws))
    grouped = fuse_mod.fuse(b.build(), rules=(fuse_mod.group_siblings,))
    assert any(isinstance(nd, ir_mod.GroupNode) for nd in grouped.nodes)
    args = (_arr(m, d), _arr(d, n), _arr(d, n), _arr(d, n))
    with gemm_account.account_gemms() as acct:
        prog = compile_graph(grouped, fuse=False)
        outs = prog(*args)
    assert len(outs) == 3
    assert len(acct.records) == 1
    (r,) = acct.records
    assert r.kind == "grouped" and r.group == 3
    assert r.plan_source == "program"           # pinned program geometry
    table = acct.table()
    assert len(table) == 1 and table[0]["grouped"] == 1
    assert "g3" in table[0]["example"]
    assert "grouped" in acct.format_table()


def test_format_table_empty_and_aggregation():
    acct = gemm_account.GemmAccountant()
    assert "no dispatches" in acct.format_table()
    acct.record_gemm(1, 256, 256, fmt="fp32", policy="mte", backend="xla")
    acct.record_gemm(1, 256, 256, fmt="fp32", policy="mte", backend="xla")
    acct.record_gemm(128, 128, 128, fmt="int8", policy="mte", backend="xla")
    rows = acct.table()
    assert [r["shape_class"] for r in rows] == ["tall_skinny", "square"]
    assert rows[0]["dispatches"] == 2
    assert "3 distinct compiled" in acct.format_table()


# -- fault firings surface on the trace ---------------------------------------


def test_fault_firing_emits_trace_instant():
    tr = tracing.install(tracing.Tracer())
    inj = FaultInjector([Fault("poison_logits", rid=0, step=1)])
    assert inj.poison_value(0, 0) is None       # before step: no firing
    assert inj.poison_value(1, 0) is not None
    tracing.uninstall()
    names = [e["name"] for e in tr.events]
    assert names == ["fault.poison_logits"]
    assert tr.events[0]["args"]["step"] == 1
    # without a tracer the same firing is silent but still recorded
    inj2 = FaultInjector([Fault("poison_logits", rid=0, step=1)])
    assert inj2.poison_value(1, 0) is not None
    assert inj2.fired


# -- pool description ---------------------------------------------------------


def test_pool_describe_structured_and_string():
    pool = KVPagePool(num_pages=8, page_size=4)
    assert pool.ensure(1, 10)      # 3 pages for 10 tokens
    d = pool.describe()
    for key in ("num_pages", "page_size", "free_pages", "used_pages",
                "sequences", "shared_pages", "cached_pages",
                "prefix_hit_pages", "prefix_queries", "cow_copies"):
        assert key in d, key
    assert d["num_pages"] == 8 and d["sequences"] == 1
    assert d["used_pages"] == 3
    # page 0 is the reserved null page: neither free nor owned
    assert d["used_pages"] + d["free_pages"] == d["num_pages"] - 1
    s = pool.describe_str()
    assert "KVPagePool" in s and "8 pages x 4" in s


# -- the engine under telemetry: observation changes nothing ------------------


def _run_engine(params, cfg, prompts, max_tokens=5):
    engine = ServingEngine(params, cfg, slots=2, cache_len=64,
                           prefill_len=16)
    for rid, p in enumerate(prompts):
        engine.submit(Request(rid=rid, prompt=p, max_tokens=max_tokens))
    outputs = engine.run()
    return engine, outputs


def test_engine_outputs_bit_identical_with_telemetry_on():
    cfg = _cfg()
    params = model_lib.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab, size=n, dtype=np.int32)
               for n in (5, 9, 13)]

    # OFF: no tracer, no accountant — the baseline
    _, base = _run_engine(params, cfg, prompts)

    # ON: tracer + accountant + fresh registry
    reset_registry()
    tracer = tracing.install(tracing.Tracer())
    acct = gemm_account.install(gemm_account.GemmAccountant())
    try:
        engine, traced = _run_engine(params, cfg, prompts)
        metrics = engine.metrics()
    finally:
        tracing.uninstall()
        gemm_account.uninstall()

    assert {r: list(v) for r, v in traced.items()} == \
        {r: list(v) for r, v in base.items()}

    # every finished request carries its own latency summary
    for resp in traced.values():
        assert resp.status == "ok"
        assert resp.metrics["tokens"] == len(resp)
        assert resp.metrics["ttft_s"] >= 0.0
        assert resp.metrics["e2e_s"] >= resp.metrics["ttft_s"]
        assert "itl_p50_s" in resp.metrics and "queue_wait_s" in resp.metrics

    # the trace holds phase spans + lifecycle instants and is schema-valid
    assert tracing.validate_trace(tracer.to_json()) == []
    names = {e["name"] for e in tracer.events}
    assert {"prefill_chunk", "decode", "sample"} <= names
    assert {"request.submit", "request.admit", "request.first_token",
            "request.finish"} <= names
    firsts = [e for e in tracer.events
              if e["name"] == "request.first_token"]
    assert len(firsts) == len(prompts)          # exactly once per request

    # latency histograms observed in the global registry
    reg = registry()
    assert reg.get("serving.ttft_s").count == len(prompts)
    assert reg.get("serving.e2e_s").count == len(prompts)
    assert reg.get("serving.inter_token_s").count > 0

    # metrics() surfaces the hidden planner/compiler caches and mirrors
    # every number as a serving.* gauge
    for key in ("plan_cache_hits", "plan_cache_misses",
                "graph_programs_compiled", "graph_program_hits"):
        assert key in metrics, key
        assert reg.get(f"serving.{key}").value == metrics[key]

    # the accountant saw the run's GEMM traffic on the Fig. 7 axis
    assert acct.records
    classes = {r.shape_class for r in acct.records}
    assert "tall_skinny" in classes             # decode/unembed GEMVs
    assert "dispatches" in acct.format_table()
