"""Chaos / fault-injection suite for the serving resilience layer.

The contract under test (ISSUE 6 acceptance criteria): under each
injected fault class — page-allocation failure, poisoned logits, chunk
exception, straggler, mid-run crash+restore — every *unaffected* request
completes with output bit-identical to a fault-free run (fp32 row
independence), every *affected* request returns a structured error
status, and ``KVPagePool.audit()`` holds after every operation.
"""
import dataclasses
import itertools
import os
import time

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.distributed.fault import Heartbeat, StragglerError, supervise
from repro.models import model as model_lib
from repro.serving import (KVPagePool, Request, ServingEngine)
from repro.serving.kv_cache import AuditError
from repro.serving.resilience import (CapacityExceeded, DeadlineExceeded,
                                      Fault, FaultInjector, PoisonedOutput,
                                      RequestError, Response, Shed,
                                      serve_with_recovery)


def _cfg():
    cfg = get_config("gemma_2b").reduced()
    return dataclasses.replace(cfg, n_layers=2, d_model=64, d_ff=128,
                               vocab=128, n_heads=2, n_kv_heads=1,
                               head_dim=32)


@pytest.fixture(scope="module")
def setup():
    cfg = _cfg()
    params = model_lib.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, size=n, dtype=np.int32)
               for n in (5, 9, 13, 7)]
    return cfg, params, prompts


def _engine(params, cfg, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("cache_len", 64)
    kw.setdefault("prefill_len", 16)
    kw.setdefault("debug_audit", True)
    return ServingEngine(params, cfg, **kw)


def _serve(params, cfg, prompts, *, max_tokens=6, engine_kw=None):
    eng = _engine(params, cfg, **(engine_kw or {}))
    for rid, p in enumerate(prompts):
        eng.submit(Request(rid=rid, prompt=p, max_tokens=max_tokens))
    out = eng.run()
    eng.sched.pool.audit()
    return eng, out


# -- Response / taxonomy -------------------------------------------------------


def test_response_is_backward_compatible_list():
    r = Response([3, 1, 4], rid=7)
    assert r == [3, 1, 4] and len(r) == 3 and r[:2] == [3, 1]
    assert r.ok and r.status == "ok" and r.rid == 7
    bad = Response([], rid=0, status="poisoned",
                   error=PoisonedOutput("x", rid=0))
    assert not bad.ok and bad.error.code == "poisoned"


def test_error_taxonomy_codes():
    assert DeadlineExceeded.code == "deadline"
    assert Shed.code == "shed"
    assert PoisonedOutput.code == "poisoned"
    assert CapacityExceeded.code == "capacity"
    for cls in (DeadlineExceeded, Shed, PoisonedOutput, CapacityExceeded):
        assert issubclass(cls, RequestError)
        assert issubclass(cls, RuntimeError)  # legacy callers still catch


# -- FaultInjector determinism -------------------------------------------------


def test_fault_plan_determinism_same_seed():
    a = FaultInjector.random_plan(7)
    b = FaultInjector.random_plan(7)
    c = FaultInjector.random_plan(8)
    assert [repr(f) for f in a.faults] == [repr(f) for f in b.faults]
    assert [repr(f) for f in a.faults] != [repr(f) for f in c.faults]


def test_fault_spec_parser():
    inj = FaultInjector.from_spec(
        "poison_logits:rid=1,step=3;alloc_fail:step=2,count=2;"
        "straggle:delay_s=0.5")
    assert [f.kind for f in inj.faults] == ["poison_logits", "alloc_fail",
                                            "straggle"]
    assert inj.faults[0].rid == 1 and inj.faults[0].step == 3
    assert inj.faults[1].count == 2
    assert inj.faults[2].delay_s == 0.5
    with pytest.raises(ValueError):
        FaultInjector.from_spec("meteor_strike:step=1")
    with pytest.raises(ValueError):
        FaultInjector.from_spec("poison_logits:severity=9")


def test_same_fault_plan_same_outputs(setup):
    """Same seed → same faults → same fired log → same outputs."""
    cfg, params, prompts = setup
    plan = "poison_logits:rid=1,step=4;alloc_fail:step=3"
    runs = []
    for _ in range(2):
        inj = FaultInjector.from_spec(plan)
        _, out = _serve(params, cfg, prompts[:3],
                        engine_kw={"fault": inj})
        runs.append((inj.fired, out))
    assert runs[0][0] == runs[1][0] and len(runs[0][0]) == 2
    assert runs[0][1] == runs[1][1]
    assert {rid: r.status for rid, r in runs[0][1].items()} \
        == {rid: r.status for rid, r in runs[1][1].items()}


# -- containment: each fault class --------------------------------------------


def test_poisoned_slot_is_quarantined_others_bit_identical(setup):
    cfg, params, prompts = setup
    _, base = _serve(params, cfg, prompts[:3])
    inj = FaultInjector([Fault("poison_logits", rid=1, step=4)])
    eng, out = _serve(params, cfg, prompts[:3], engine_kw={"fault": inj})
    assert out[1].status == "poisoned" and len(out[1]) < len(base[1])
    assert isinstance(out[1].error, PoisonedOutput)
    # unaffected rows decode on, bit-identical (fp32 row independence)
    for rid in (0, 2):
        assert out[rid].status == "ok" and list(out[rid]) == list(base[rid])
    assert eng.metrics()["cancelled_requests"] == 1
    assert eng.metrics()["free_pages"] == eng.metrics()["num_pages"] - 1


def test_poisoned_slot_on_stateful_arch(setup):
    """Quarantine + row-valid masks on an arch with ring/recurrent
    per-slot state: the poisoned slot cancels, survivors bit-identical."""
    cfg = dataclasses.replace(get_config("recurrentgemma_9b").reduced(),
                              vocab=128)
    params = model_lib.init_params(jax.random.PRNGKey(0), cfg)
    _, _, prompts = setup
    _, base = _serve(params, cfg, prompts[:3])
    inj = FaultInjector([Fault("poison_logits", rid=0, step=5)])
    _, out = _serve(params, cfg, prompts[:3], engine_kw={"fault": inj})
    assert out[0].status == "poisoned"
    for rid in (1, 2):
        assert out[rid].status == "ok" and list(out[rid]) == list(base[rid])


def test_chunk_exception_contained_to_one_request(setup):
    cfg, params, prompts = setup
    _, base = _serve(params, cfg, prompts[:3])
    inj = FaultInjector([Fault("chunk_exception", rid=2)])
    eng, out = _serve(params, cfg, prompts[:3], engine_kw={"fault": inj})
    assert out[2].status == "error" and list(out[2]) == []
    assert out[2].error.rid == 2
    for rid in (0, 1):
        assert out[rid].status == "ok" and list(out[rid]) == list(base[rid])
    eng.sched.pool.audit()


def test_chunk_compile_error_propagates(setup, monkeypatch):
    """A prefill program that fails to compile is a defect of the
    engine, not of one request: it must escape ``run()`` instead of
    being turned into per-request error statuses."""
    cfg, params, prompts = setup
    eng = _engine(params, cfg)

    def broken(*args):
        raise RuntimeError("Mosaic failed to compile TPU kernel")

    monkeypatch.setattr(eng, "_chunk_fn", lambda fmt, c: broken)
    eng.submit(Request(rid=0, prompt=prompts[0], max_tokens=4))
    with pytest.raises(RuntimeError, match="Mosaic"):
        eng.run()


def test_alloc_failure_defers_without_corruption(setup):
    """An injected page-allocation failure exercises the deferral /
    eviction path; every request still completes and never-preempted
    requests are bit-identical to the fault-free run."""
    cfg, params, prompts = setup
    _, base = _serve(params, cfg, prompts[:3], max_tokens=8)
    inj = FaultInjector([Fault("alloc_fail", step=2, count=3)])
    eng, out = _serve(params, cfg, prompts[:3], max_tokens=8,
                      engine_kw={"fault": inj})
    assert any(k == "alloc_fail" for _, k, _ in inj.fired)
    assert eng.sched.pool.injected_alloc_failures >= 1
    preempted = {rid for kind, rid in eng.sched.events if kind == "preempt"}
    for rid in range(3):
        assert out[rid].status == "ok" and len(out[rid]) == 8
        if rid not in preempted:
            assert list(out[rid]) == list(base[rid])


def test_straggler_watchdog_triggers_supervised_restart(setup):
    """The straggle must out-sleep the watchdog deadline by more than
    its 0.5 s poll, and the deadline must comfortably exceed a worst-case
    *healthy* step (which includes first-call compilation)."""
    cfg, params, prompts = setup
    inj = FaultInjector([Fault("straggle", step=2, delay_s=7.0)])

    def make_engine():
        return _engine(params, cfg, fault=inj, watchdog_s=5.0)

    reqs = [Request(rid=i, prompt=p, max_tokens=4)
            for i, p in enumerate(prompts[:2])]
    out = serve_with_recovery(make_engine, reqs, max_restarts=2,
                              backoff_s=0.0, log=lambda *a: None)
    assert any(k == "straggle" for _, k, _ in inj.fired)
    assert all(out[i].status == "ok" and len(out[i]) == 4 for i in range(2))


def test_crash_snapshot_restore_completes_everything(setup):
    """Mid-run crash: completed-before-crash and not-yet-admitted
    requests end bit-identical to a fault-free run; mid-flight requests
    re-admit through the prefix re-attachment path and finish with full
    token counts and ok status."""
    cfg, params, prompts = setup
    _, base = _serve(params, cfg, prompts, max_tokens=6)
    inj = FaultInjector([Fault("crash", step=4)])
    engines = []

    def make_engine():
        eng = _engine(params, cfg, fault=inj)
        engines.append(eng)
        return eng

    reqs = [Request(rid=i, prompt=p, max_tokens=6)
            for i, p in enumerate(prompts)]
    out = serve_with_recovery(make_engine, reqs, max_restarts=2,
                              backoff_s=0.0, log=lambda *a: None)
    assert len(engines) == 2, "exactly one restart"
    crashed, resumed = engines
    assert any(k == "crash" for _, k, _ in inj.fired)
    for rid in range(4):
        assert out[rid].status == "ok" and len(out[rid]) == 6
    # whatever the first engine finished or never started is bit-identical
    mid_flight = {r.rid for r in crashed.slot_req if r is not None} \
        | {e.rid for e in crashed.sched.waiting} \
        | {e.rid for e in crashed.sched.active.values()}
    untouched = set(range(4)) - mid_flight
    for rid in untouched:
        assert list(out[rid]) == list(base[rid])
    # mid-flight requests kept their pre-crash tokens as a prefix (the
    # snapshot carries partial outputs; resume appends, never rewrites)
    snap = crashed.snapshot()
    for rd in snap["requests"]:
        assert list(out[rd["rid"]])[:len(rd["output"])] == rd["output"]
    resumed.sched.pool.audit()


def test_recovery_keeps_donated_pool_after_chunk_fault(setup):
    """The chunk and decode programs take the pool donated.  A chunk
    fault (raised before the call), then a crash: the restarted engine
    restores onto the surviving pool, re-attaches its published pages,
    and every request finishes — the faulted one with its error, the
    others with the fault-free greedy tokens."""
    cfg, params, prompts = setup
    kw = dict(prefill_chunk=8, page_size=8)
    _, base = _serve(params, cfg, prompts, max_tokens=6, engine_kw=kw)
    inj = FaultInjector([Fault("chunk_exception", rid=1, chunk=1),
                         Fault("crash", step=5)])
    engines = []

    def make_engine():
        engines.append(_engine(params, cfg, fault=inj, **kw))
        return engines[-1]

    reqs = [Request(rid=i, prompt=p, max_tokens=6)
            for i, p in enumerate(prompts)]
    out = serve_with_recovery(make_engine, reqs, max_restarts=2,
                              backoff_s=0.0, keep_cache=True,
                              log=lambda *a: None)
    assert [k for _, k, _ in inj.fired] == ["chunk_exception", "crash"]
    assert len(engines) == 2
    assert engines[1].sched.pool.prefix_hit_pages > 0
    assert out[1].status == "error"
    for rid in (0, 2, 3):
        assert out[rid].status == "ok" and list(out[rid]) == list(base[rid])
    engines[1].sched.pool.audit()


def test_recovery_drops_pool_consumed_by_failed_chunk(setup, monkeypatch):
    """A failure raised inside the chunk program, after it took the pool
    donated, leaves the engine naming deleted buffers: recovery restarts
    on a fresh pool instead of restoring onto them, and every request
    finishes with the fault-free greedy tokens."""
    cfg, params, prompts = setup
    kw = dict(prefill_chunk=8, page_size=8)
    _, base = _serve(params, cfg, prompts, max_tokens=6, engine_kw=kw)
    real = ServingEngine._chunk_fn
    failed, engines = [], []

    def chunk_fn(self, format_policy, chunk_idx):
        fn = real(self, format_policy, chunk_idx)

        def failing(p, b, c):
            out = fn(p, b, c)
            if chunk_idx == 1 and not failed:
                failed.append(chunk_idx)
                raise RuntimeError("chunk program failed after dispatch")
            return out
        return failing

    monkeypatch.setattr(ServingEngine, "_chunk_fn", chunk_fn)
    real_restore = ServingEngine.restore
    restored_with = []

    def restore(self, snap, *, cache=None):
        restored_with.append(cache)
        return real_restore(self, snap, cache=cache)

    monkeypatch.setattr(ServingEngine, "restore", restore)

    def make_engine():
        engines.append(_engine(params, cfg, **kw))
        return engines[-1]

    reqs = [Request(rid=i, prompt=p, max_tokens=6)
            for i, p in enumerate(prompts)]
    out = serve_with_recovery(make_engine, reqs, max_restarts=2,
                              backoff_s=0.0, keep_cache=True,
                              log=lambda *a: None)
    assert failed and len(engines) == 2
    assert any(leaf.is_deleted()
               for leaf in jax.tree.leaves(engines[0].cache))
    assert restored_with == [None]
    for rid in range(len(prompts)):
        assert out[rid].status == "ok" and list(out[rid]) == list(base[rid])
    engines[1].sched.pool.audit()


def test_snapshot_restore_reattaches_published_pages(setup):
    """With the device cache carried across the restart, the snapshot's
    page registrations are restored into the fresh pool, so a restored
    request whose prefill window is unchanged (here: a waiting request
    sharing the crashed request's prompt) aliases the published KV
    through the prefix cache instead of recomputing it."""
    cfg, params, prompts = setup
    kw = dict(slots=1, prefill_chunk=8, page_size=8)
    eng = _engine(params, cfg, **kw)
    eng.submit(Request(rid=0, prompt=prompts[0], max_tokens=6))
    eng.submit(Request(rid=1, prompt=prompts[0], max_tokens=6))  # same prompt
    for _ in range(2):   # rid0 prefills both chunks, publishing page 0
        eng._admit()
        eng.step()
    snap = eng.snapshot()
    assert snap["requests"] and snap["published"]
    eng2 = _engine(params, cfg, **kw)
    eng2.restore(snap, cache=eng.cache)
    out = eng2.run()
    eng2.sched.pool.audit()
    assert all(out[i].status == "ok" and len(out[i]) == 6 for i in range(2))
    assert eng2.sched.pool.prefix_hit_pages > 0, \
        "restore must re-attach published pages through the prefix cache"


def test_restore_rejects_mismatched_geometry(setup):
    cfg, params, prompts = setup
    eng = _engine(params, cfg)
    snap = eng.snapshot()
    other = _engine(params, cfg, page_size=8)
    with pytest.raises(ValueError, match="geometry"):
        other.restore(snap)


# -- deadlines / shedding ------------------------------------------------------


class _FakeClock:
    """Monotonic fake: every read advances 10 ms."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 0.01
        return self.t


def test_deadline_cancels_late_request_with_partial_output(setup):
    cfg, params, prompts = setup
    _, base = _serve(params, cfg, prompts[:3], max_tokens=12)
    eng = _engine(params, cfg, clock=_FakeClock())
    eng.submit(Request(rid=0, prompt=prompts[0], max_tokens=12,
                       deadline_ms=150.0))
    for rid in (1, 2):
        eng.submit(Request(rid=rid, prompt=prompts[rid], max_tokens=12))
    out = eng.run()
    eng.sched.pool.audit()
    assert out[0].status == "deadline" and len(out[0]) < 12
    assert isinstance(out[0].error, DeadlineExceeded)
    for rid in (1, 2):
        assert out[rid].status == "ok" and list(out[rid]) == list(base[rid])
    assert eng.metrics()["free_pages"] == eng.metrics()["num_pages"] - 1


def test_shed_bounded_queue_depth(setup):
    cfg, params, prompts = setup
    eng = _engine(params, cfg, shed_queue_depth=3)
    eng.submit(Request(rid=0, prompt=prompts[0], max_tokens=4))
    eng.submit(Request(rid=1, prompt=prompts[1], max_tokens=4))
    eng.submit(Request(rid=2, prompt=prompts[2], max_tokens=4))
    with pytest.raises(Shed):  # 4th submit sees queue depth 3
        eng.submit(Request(rid=3, prompt=prompts[3], max_tokens=4))
    out = eng.run()
    assert out[3].status == "shed" and list(out[3]) == []
    assert all(out[i].status == "ok" and len(out[i]) == 4 for i in range(3))
    assert eng.metrics()["shed_requests"] == 1


def test_shed_token_watermark(setup):
    cfg, params, prompts = setup
    # each request commits prefill_len(16) + max_tokens(6) = 22 slots
    eng = _engine(params, cfg, shed_token_watermark=50)
    eng.submit(Request(rid=0, prompt=prompts[0], max_tokens=6))
    eng.submit(Request(rid=1, prompt=prompts[1], max_tokens=6))
    with pytest.raises(Shed, match="watermark"):
        eng.submit(Request(rid=2, prompt=prompts[2], max_tokens=6))
    out = eng.run()
    assert out[2].status == "shed"
    assert all(out[i].status == "ok" for i in range(2))


# -- KVPagePool chaos ----------------------------------------------------------


def test_pool_audit_catches_corruption():
    pool = KVPagePool(num_pages=8, page_size=4)
    pool.audit()
    assert pool.ensure(1, 8)
    pool.audit()
    pool._ref[pool.pages_of(1)[0]] += 1  # simulate refcount drift
    with pytest.raises(AuditError, match="refcount"):
        pool.audit()


def test_pool_chaos_stress_audit_after_every_op():
    """Seeded random alias/evict/CoW/resume traffic; every operation
    leaves the pool in an audit-clean state."""
    rng = np.random.default_rng(42)
    pool = KVPagePool(num_pages=24, page_size=4)
    keys = itertools.count(1)
    live = {}            # key -> tokens granted
    registered = []      # hashes in registration order
    for step in range(500):
        op = rng.choice(["new", "grow", "release", "register", "admit",
                         "cow", "inject", "lookup"])
        if op == "new":
            key, tok = next(keys), int(rng.integers(1, 33))
            if pool.ensure(key, tok):
                live[key] = tok
        elif op == "grow" and live:
            key = int(rng.choice(list(live)))
            tok = live[key] + int(rng.integers(1, 17))
            if pool.ensure(key, tok):
                live[key] = tok
        elif op == "release" and live:
            key = int(rng.choice(list(live)))
            pool.release(key)
            del live[key]
        elif op == "register" and live:
            key = int(rng.choice(list(live)))
            idx = int(rng.integers(0, len(pool.pages_of(key))))
            h = f"h{key}:{idx}:{step}"
            if pool.register(key, idx, h):
                registered.append(h)
        elif op == "admit" and registered:
            n = int(rng.integers(1, 4))
            hashes = [h for h in registered if h in pool._page_of][:n]
            matched = pool.lookup_prefix(hashes)
            key = next(keys)
            tok = max(matched * pool.page_size, 1) + int(rng.integers(0, 9))
            if pool.admit_prefix(key, hashes, matched, tok):
                live[key] = tok
        elif op == "cow" and live:
            key = int(rng.choice(list(live)))
            pages = pool.pages_of(key)
            shared = [i for i, p in enumerate(pages) if pool.ref_of(p) > 1]
            if shared:
                try:
                    pool.make_private(key, shared[0])
                except RuntimeError:
                    pass  # pool dry: legitimate refusal, state unchanged
        elif op == "inject":
            pool.inject_alloc_failures += 1
            key, before = next(keys), pool.free_pages
            assert not pool.ensure(key, 4)
            assert pool.free_pages == before and pool.pages_of(key) == []
        elif op == "lookup":
            pool.lookup_prefix([f"nope{step}", "nope2"])
        pool.audit()
    assert registered and live  # the walk actually exercised sharing


def test_injected_alloc_failure_is_all_or_nothing():
    pool = KVPagePool(num_pages=8, page_size=4)
    pool.inject_alloc_failures = 1
    assert not pool.ensure(1, 8)
    pool.audit()
    assert pool.ensure(1, 8)   # consumed: next grant succeeds
    pool.audit()
    assert pool.injected_alloc_failures == 1


# -- distributed/fault.py satellites ------------------------------------------


def test_heartbeat_beat_is_atomic(tmp_path):
    path = str(tmp_path / "hb")
    hb = Heartbeat(path, interval_s=60.0)
    hb.stop()
    hb.beat()
    assert float(open(path).read()) > 0
    leftovers = [f for f in os.listdir(tmp_path) if f.startswith("hb.tmp")]
    assert not leftovers, "temp file must be replaced, not left behind"


def test_supervise_on_give_up_hook():
    seen = []

    def run(attempt):
        raise StragglerError(f"hang {attempt}")

    with pytest.raises(StragglerError, match="hang 2"):
        supervise(run, max_restarts=2, backoff_s=0.0,
                  log=lambda *a: None, on_give_up=seen.append)
    assert len(seen) == 1 and isinstance(seen[0], StragglerError)
