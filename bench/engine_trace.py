"""The serving engine's own spans and programs in a recorded trace.

A traced run's ``.xplane.pb`` holds, besides what :mod:`bench.trace`
reads, the engine's host spans (``repro.telemetry.tracing`` spans are
``jax.profiler`` annotations while a tracer is installed, as the harness
installs one for the traced part) and the device's ``XLA Modules`` line,
one event per run of a jitted program. From them, inside the traced
window:

- each program's median device time, by the program's name up to the
  ``(``: ``jit_serve_decode``, ``jit_serve_prefill_chunk``, ...;
- the host gap: device-idle time inside ``step`` spans, per step;
- the idle split: every idle instant of the window put down to the
  innermost engine span that covers it (a ``step`` with no child covering
  it counts under ``step``, an instant under no engine span under
  ``(none)``), in milliseconds per step.

Like :mod:`bench.trace`, the reductions work on plain intervals and are
checked on synthetic ones; :func:`load` turns a recorded trace into
them, and :func:`of_run` finds the trace of the run being reported.
A program without these spans or names (an engine from before they
existed) gives None for each.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import gzip
import statistics
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

from bench import trace
from bench.trace import Span

# The engine's host spans (``repro.serving.engine``), strictly nested
# under ``step`` on the thread that drives the engine.
ENGINE_SPANS = ("step", "admit", "prefill_chunk", "device_wait", "sample",
                "evict", "decode_launch", "observe", "draft", "verify")
STEP = "step"
NONE = "(none)"
DECODE = "jit_serve_decode"
PREFILL_CHUNK = "jit_serve_prefill_chunk"
MODULES_LINE = "XLA Modules"
# Where ``bench/run.py`` has the profiler write a traced run's trace.
TRACES = Path(__file__).resolve().parents[1] / ".bench_runs" / "trace"


@dataclasses.dataclass(frozen=True)
class Engine:
    steps: int
    idle_ms: float                  # device-idle time of the window
    decode_ms: Optional[float]      # median device time of a decode step
    chunk_ms: Optional[float]       # median of a prefill chunk program
    host_gap_ms: Optional[float]    # idle inside ``step`` spans, per step
    idle_by_span: Dict[str, float]  # idle ms per step, innermost span


def innermost(spans: Sequence[Span], lo: float, hi: float
              ) -> List[Tuple[float, float, str]]:
    """[lo, hi] cut into consecutive pieces, each named after the
    innermost span covering it (the latest to start), or :data:`NONE`."""
    pieces: List[Tuple[float, float, str]] = []
    stack: List[Span] = []
    t = lo

    def cover(until: float):
        nonlocal t
        until = min(until, hi)
        if until > t:
            pieces.append((t, until, stack[-1].name if stack else NONE))
            t = until

    for sp in sorted(spans, key=lambda s: (s.start, -s.end)):
        while stack and stack[-1].end <= sp.start:
            cover(stack[-1].end)
            stack.pop()
        cover(sp.start)
        stack.append(sp)
    while stack:
        cover(stack[-1].end)
        stack.pop()
    cover(hi)
    return pieces


def split(idle: Sequence[Tuple[float, float]],
          pieces: Sequence[Tuple[float, float, str]]) -> Dict[str, float]:
    """Seconds of the sorted, disjoint ``idle`` stretches in each named
    piece of :func:`innermost`."""
    out: Dict[str, float] = collections.defaultdict(float)
    j = 0
    for a, b in idle:
        while j < len(pieces) and pieces[j][1] <= a:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < b:
            s, e, name = pieces[k]
            out[name] += min(b, e) - max(a, s)
            k += 1
    return dict(out)


def module_median_s(modules: Sequence[Span], name: str, lo: float,
                    hi: float) -> Optional[float]:
    """Median duration of the runs of program ``name`` that lie wholly
    inside [lo, hi]; None where none does."""
    times = [m.end - m.start for m in modules
             if m.name == name and lo <= m.start and m.end <= hi]
    return statistics.median(times) if times else None


def reduce(ops: Sequence[trace.Op], spans: Sequence[Span],
           modules: Sequence[Span], lo: float, hi: float) -> Engine:
    """The engine's numbers from the device operations, the engine's host
    spans and the program runs of one trace, inside [lo, hi]."""
    inside = [trace.Op(max(o.start, lo), min(o.end, hi), o.name, o.category)
              for o in ops if o.end > lo and o.start < hi]
    idle = trace.idle_intervals(inside, lo, hi)
    steps = [sp for sp in spans if sp.name == STEP and lo <= sp.start < hi]
    n = len(steps)
    by_span = split(idle, innermost(spans, lo, hi))
    gap = split(idle, innermost(steps, lo, hi)).get(STEP, 0.0)

    def ms(s):
        return None if s is None else 1e3 * s

    return Engine(
        steps=n, idle_ms=1e3 * sum(e - s for s, e in idle),
        decode_ms=ms(module_median_s(modules, DECODE, lo, hi)),
        chunk_ms=ms(module_median_s(modules, PREFILL_CHUNK, lo, hi)),
        host_gap_ms=1e3 * gap / n if n else None,
        idle_by_span={k: 1e3 * v / n for k, v in by_span.items()}
        if n else {})


# -- reading a recorded trace ------------------------------------------------


def load(path: Path, device: str = "/device:TPU:0"
         ) -> Tuple[List[Span], List[Span]]:
    """The engine's host spans (names in :data:`ENGINE_SPANS`) and the
    runs of ``device``'s programs, each named up to its ``(``, from an
    ``.xplane.pb`` (optionally gzipped); field numbers as in
    :mod:`bench.trace`."""
    path = Path(path)
    opener = gzip.open if path.suffix == ".gz" else open
    with opener(path, "rb") as f:
        space = trace._fields(f.read())
    spans: List[Span] = []
    modules: List[Span] = []
    for raw_plane in space.get(1, []):
        plane = trace._fields(raw_plane)
        name = trace._text(plane, 2)
        if name != device and not name.startswith("/host:"):
            continue
        names = {k: trace._text(v, 2)
                 for k, v in trace._map(plane, 4).items()}
        if name == device:
            for raw_line in plane.get(3, []):
                line = trace._fields(raw_line)
                if trace._text(line, 2) == MODULES_LINE:
                    modules += [Span(s, e, names.get(mid, "").split("(")[0])
                                for mid, s, e, _ in trace._events(line)]
            continue
        wanted = {k for k, v in names.items() if v in ENGINE_SPANS}
        if not wanted:
            continue
        for raw_line in plane.get(3, []):
            spans += [Span(s, e, names[mid]) for mid, s, e, _ in
                      trace._events(trace._fields(raw_line))
                      if mid in wanted]
    return spans, modules


@functools.lru_cache(maxsize=4)
def _read(path: str, _stamp: Tuple[int, int]
          ) -> Optional[Tuple[float, Engine]]:
    ops, _, window = trace.load(Path(path))
    if window is None:
        return None
    spans, modules = load(Path(path))
    return (window.end - window.start,
            reduce(ops, spans, modules, window.start, window.end))


def of_run(run) -> Optional[Engine]:
    """The engine's numbers in the trace of ``run``: the newest trace
    under :data:`TRACES` whose traced window is as long as the one
    ``run``'s summary read. None for a run without a trace."""
    if run.trace is None:
        return None
    found = TRACES.glob("*/plugins/profile/*/*.xplane.pb")
    for path in sorted(found, key=lambda p: -p.stat().st_mtime):
        st = path.stat()
        got = _read(str(path), (st.st_mtime_ns, st.st_size))
        if got is not None and abs(got[0] - run.trace.window_s) < 1e-9:
            return got[1]
    return None
