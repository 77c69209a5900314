"""What the metric readers read: one run's records, and helpers over them."""
from __future__ import annotations

import dataclasses
from typing import List, Optional

import numpy as np

from bench import work
from bench.harness import Window
from bench.peaks import Peaks
from bench.trace import Summary


@dataclasses.dataclass
class Run:
    window: Window
    shape: object             # the cell's reference module's shape
    peaks: Peaks
    sizing: dict
    setup_s: float
    trace: Optional[Summary] = None


def percentile(values: List[float], q: float) -> Optional[float]:
    """The q-th percentile (linear between order statistics); None for
    no values."""
    if not values:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def delta(run: Run, key: str, traced: bool = False) -> float:
    """A cumulative engine counter's growth across the window (or across
    the traced part of it)."""
    if traced:
        t = run.window.traced
        return t["counters_close"][key] - t["counters_open"][key]
    return run.window.counters_close[key] - run.window.counters_open[key]


def stamps_in(run: Run, lo: float, hi: float):
    """(track, index) of every output token delivered in (lo, hi]."""
    for t in run.window.tracks:
        for j, s in enumerate(t.stamps):
            if lo < s <= hi:
                yield t, j


def ttfts(run: Run) -> List[float]:
    """Time to first token of every request due in the window, from when
    it was due; a request with no token by the close counts the time it
    had waited."""
    w = run.window
    out = []
    for t in w.tracks:
        if not (w.open <= t.due < w.close):
            continue
        first = t.stamps[0] if t.stamps else None
        out.append((first if first is not None and first <= w.close
                    else w.close) - t.due)
    return out


def token_gaps(run: Run) -> List[float]:
    """Gaps between consecutive output tokens of a request, for every
    token delivered in the window that had one before it."""
    w = run.window
    return [t.stamps[j] - t.stamps[j - 1]
            for t, j in stamps_in(run, w.open, w.close) if j >= 1]


def expert_counts(run: Run, traced: bool = False) -> Optional[tuple]:
    """The growth of the engine's expert counters (:data:`work.COUNTERS`)
    across the window (or its traced part); None where it reports none."""
    c = run.window.traced["counters_close"] if traced \
        else run.window.counters_close
    if not all(k in c for k in work.COUNTERS):
        return None
    return tuple(delta(run, k, traced) for k in work.COUNTERS)


def traced_work(run: Run) -> Optional[work.Work]:
    """The weight-bearing matrix products that the traced part of the
    window needed: the decode steps and prefill chunks that ran between
    its two flushes, with the expert work the engine's counters report or,
    without them, what is certain."""
    tr = run.window.traced
    if tr is None:
        return None
    e = run.sizing["engine"]
    pk, bw = run.peaks.bf16_flops, run.peaks.hbm_bw
    counted = expert_counts(run, traced=True)
    tokens = sum(1 for _, j in stamps_in(run, tr["open"], tr["close"])
                 if j >= 1)
    w = work.decode_steps(run.shape, int(delta(run, "decode_steps", True)),
                          tokens, pk, bw, experts=counted is None)
    one = work.chunk(run.shape, e["prefill_chunk"], pk, bw,
                     experts=counted is None)
    w = w + one * tr["chunks"]
    if counted is not None:
        w = w + work.routed(run.shape, *counted, pk, bw)
    return w


def useful_flops(run: Run) -> float:
    """Operations of the window's useful work, with no padding: the
    prompt of every request whose first token came in the window, and
    every output token delivered in it. Output token j >= 1 needs the
    forward of token j - 1 over the prompt and j earlier tokens; every
    output token needs one head. Expert work is the engine's counters'
    over the window where it reports them, and else what is certain."""
    w, s = run.window, run.shape
    counted = expert_counts(run)
    per_token = work.token_flops(s, experts=counted is None)
    total = 0.0
    for t, j in stamps_in(run, w.open, w.close):
        p = min(len(t.gen.prompt), run.sizing["engine"]["prefill_len"])
        total += work.head_flops(s)
        if j == 0:
            total += p * per_token + work.prompt_attention_flops(s, p)
        else:
            total += per_token + work.attention_flops(s, p + j)
    if counted is not None:
        total += work.routed(s, *counted, run.peaks.bf16_flops,
                             run.peaks.hbm_bw).flops
    return total
