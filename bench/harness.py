"""Driving the serving engine through one measured window.

The engine is built as ``launch/serve.py`` builds it, with the cell's
sizing and no route knob, from weights made on the device from the seed.
Set-up warms up exactly the programs the cell's traffic uses (every
prefill chunk index, the decode step, the first-token sampling), then,
for a backlog, runs a fixed number of steps so the window opens in steady
state. The window drives ``submit`` and ``step`` from the harness's own
loop and stamps each output token when it becomes visible on the host,
as a client would see it. Open-loop requests are timed from when they
were due.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

import jax
import numpy as np

from bench import generator
from bench.spec import Cell

clock = time.perf_counter


def span(name: str):
    """A host span on the profiler's clock (free when no trace runs)."""
    return jax.profiler.TraceAnnotation(name)


@dataclasses.dataclass
class Track:
    gen: generator.Req
    req: object               # the engine's Request
    due: float                # absolute, on ``clock``
    submitted: Optional[float] = None
    stamps: List[float] = dataclasses.field(default_factory=list)
    done_at: Optional[float] = None


@dataclasses.dataclass
class Window:
    open: float
    close: float
    tracks: List[Track]
    counters_open: Dict[str, float]
    counters_close: Dict[str, float]
    steps: int
    compiles: int
    late_max_s: float
    late_mean_s: float
    statuses: Dict[int, str]
    traced: Optional[dict] = None     # counters and times around the trace

    @property
    def seconds(self) -> float:
        return self.close - self.open


class CompileCounter:
    """Counts executables obtained (compiled or loaded from the
    persistent cache) while it is on."""

    COMPILED = "/jax/core/compile/backend_compile_duration"
    LOADED = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self.count = 0
        self.on = False
        jax.monitoring.register_event_duration_secs_listener(self._timed)
        jax.monitoring.register_event_listener(self._event)

    def _timed(self, event, _secs, **_kw):
        if self.on and event == self.COMPILED:
            self.count += 1

    def _event(self, event, **_kw):
        if self.on and event == self.LOADED:
            self.count += 1


def make_params(cfg, ref, shape, seed: int):
    """The program's serving parameters, made by the benchmark from the
    seed in one jitted call. The tree's structure and dtypes are the
    program's (its initialisation, traced and never run); every leaf is
    the weight that the cell's reference module ``ref`` makes for it
    (``ref.layer_weights``, ``ref.head_weights``), so the reference makes
    the same weights again without taking any from the program.

    A group's leaves are stacked over the scanned groups: position ``j``
    of group ``g`` is layer ``g * period + j``, and tail layer ``i``
    follows the last group."""
    from repro.serving.engine import init_serving_params
    key = ref.seed_key(seed)
    like = jax.eval_shape(lambda k: init_serving_params(k, cfg), key)
    groups = cfg.n_layers // cfg.period if cfg.scan_layers else 0

    def build(key):
        small = jax.random.fold_in(key, 0x5EED)
        head = ref.head_weights(shape, key, small)
        layers = {}

        def layer(part, i):
            if (part, i) not in layers:
                at = (range(i, groups * cfg.period, cfg.period)
                      if part == "groups" else groups * cfg.period + i)
                layers[part, i] = ref.layer_weights(shape, key, small, at)
            return layers[part, i]

        suffix = {"w": "", "b": "_b", "scale": "", "bias": "_b"}

        def leaf(path, x):
            p = tuple(getattr(k, "key", getattr(k, "idx", None))
                      for k in path)
            v = None
            if p in (("embedding", "table"), ("embedding", "head")):
                v = head[p[1]]
            elif p[0] == "final_norm":
                v = head["final" + suffix[p[1]]]
            elif p[0] in ("groups", "tail") and len(p) > 3:
                if p[2] in ("mixer", "ffn"):
                    name = p[3] + (suffix[p[4]] if len(p) > 4 else "")
                elif p[2] in ("norm1", "norm2"):
                    name = p[2] + suffix[p[3]]
                else:
                    name = None
                v = layer(p[0], p[1]).get(name)
            if v is None:
                raise ValueError(f"no benchmark weight for the program's "
                                 f"parameter {jax.tree_util.keystr(path)}")
            if v.shape != x.shape:
                raise ValueError(f"{jax.tree_util.keystr(path)}: the "
                                 f"program holds {x.shape}, the benchmark "
                                 f"makes {v.shape}")
            return v.astype(x.dtype)

        return jax.tree_util.tree_map_with_path(leaf, like)

    return jax.jit(build)(key)


def build_engine(cell: Cell, cfg, params, seed: int):
    from repro.serving.engine import ServingEngine
    e = cell.sizing["engine"]
    return ServingEngine(params, cfg, slots=e["slots"],
                         cache_len=e["cache_len"],
                         prefill_len=e["prefill_len"],
                         page_size=e["page_size"],
                         prefill_chunk=e["prefill_chunk"],
                         seed=seed & 0x7FFFFFFF)


def _request(g: generator.Req):
    from repro.serving.engine import Request
    return Request(rid=g.rid, prompt=g.prompt, max_tokens=g.max_tokens,
                   temperature=0.0)


def warm_up(engine, cell: Cell, seed: int, vocab: int):
    """Compile every program the window will run: one request of a full
    window runs every prefill chunk index, its first token the sampling
    program, its second the decode step. Two requests, so decode also
    runs with more than one row."""
    rng = np.random.default_rng([seed, 1])
    n = cell.sizing["engine"]["prefill_len"]
    for i in range(2):
        engine.submit(_request(generator.Req(
            -1 - i, 0.0, rng.integers(1, vocab, n, dtype=np.int32), 3)))
    engine.run()


def run_window(engine, reqs: List[generator.Req], seconds: float, *,
               counter: CompileCounter, preroll_steps: int = 0,
               trace_at: Optional[float] = None,
               trace_seconds: float = 0.0,
               trace_dir: Optional[str] = None) -> Window:
    """Serve ``reqs`` for ``seconds``. Backlog requests (due 0) are
    submitted before ``preroll_steps`` steps that precede the window.
    With ``trace_dir``, the profiler records ``trace_seconds`` from
    ``trace_at`` seconds into the window, between two pipeline flushes."""
    from repro.telemetry import tracing
    tracks: List[Track] = []
    live: List[Track] = []
    backlog = [g for g in reqs if g.due <= 0.0]
    timed = [g for g in reqs if g.due > 0.0]

    def submit(g, due, now):
        t = Track(g, _request(g), due)
        engine.submit(t.req)
        t.submitted = now
        tracks.append(t)
        live.append(t)

    def observe(now):
        for t in list(live):
            n = len(t.req.output)
            if n > len(t.stamps):
                t.stamps.extend([now] * (n - len(t.stamps)))
            if t.req.done:
                t.done_at = now
                live.remove(t)

    def has_work():
        return engine.sched.has_work or engine.steps_in_flight

    now = clock()
    for g in backlog:
        submit(g, now, now)
    for _ in range(preroll_steps):
        engine.step()
        observe(clock())
    counters_open = counters(engine)
    counter.on = True
    t0 = clock()
    end = t0 + seconds
    for t in tracks:       # what the pre-roll delivered is before the window
        t.due = t0
    i, late, steps = 0, [], 0
    traced, tr_span = None, None
    tr_state = "off" if trace_dir is None else "wait"

    def trace_open():
        _settle(engine)
        observe(clock())
        jax.profiler.start_trace(trace_dir)
        sp = span("bench.traced")
        sp.__enter__()
        # The program's own spans count the prefill chunks that run.
        tracing.install(tracing.Tracer(clock=clock))
        return sp, {"open": clock(), "counters_open": counters(engine)}

    def trace_close():
        _settle(engine)
        now = clock()
        observe(now)
        events = tracing.active().events
        tracing.uninstall()
        traced.update(close=now, counters_close=counters(engine),
                      chunks=sum(1 for ev in events
                                 if ev["name"] == "prefill_chunk"))
        tr_span.__exit__(None, None, None)
        jax.profiler.stop_trace()

    while True:
        now = clock()
        if now >= end:
            break
        if tr_state == "wait" and now >= t0 + trace_at:
            tr_span, traced = trace_open()
            tr_state = "on"
        elif tr_state == "on" and now >= traced["open"] + trace_seconds:
            trace_close()
            tr_state = "done"
        with span("bench.submit"):
            while i < len(timed) and t0 + timed[i].due <= now:
                late.append(now - (t0 + timed[i].due))
                submit(timed[i], t0 + timed[i].due, now)
                i += 1
        if has_work():
            with span("bench.step"):
                engine.step()
            steps += 1
            with span("bench.deliver"):
                observe(clock())
        else:
            wake = [end, t0 + timed[i].due if i < len(timed) else end]
            if tr_state == "wait":
                wake.append(t0 + trace_at)
            elif tr_state == "on":
                wake.append(traced["open"] + trace_seconds)
            with span("bench.wait"):
                time.sleep(max(0.0, min(wake) - clock()))
    close = clock()
    counter.on = False
    if tr_state == "on":
        trace_close()
    counters_close = counters(engine)
    # Deliver what is in flight (after the close: not counted) and read
    # every request's final status.
    statuses = {rid: r.status for rid, r in engine.run(max_steps=0).items()}
    return Window(open=t0, close=close, tracks=tracks,
                  counters_open=counters_open, counters_close=counters_close,
                  steps=steps, compiles=counter.count,
                  late_max_s=max(late, default=0.0),
                  late_mean_s=float(np.mean(late)) if late else 0.0,
                  statuses=statuses, traced=traced)


def counters(engine) -> Dict[str, float]:
    """The engine's cumulative counts, with the scheduler's sum of active
    rows over decode steps."""
    m = engine.metrics()
    m["active_step_sum"] = engine.sched.active_step_sum
    return m


def _settle(engine):
    """Deliver every launched step and wait for the device, so counters
    read at this point cover exactly the device work done so far."""
    engine.run(max_steps=0)
    jax.block_until_ready(engine.cache)
