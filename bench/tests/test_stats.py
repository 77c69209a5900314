"""The readers' arithmetic on a window whose answers are known."""
from pathlib import Path

import numpy as np
import pytest

from bench import generator, spec, stats, work
from bench.harness import Track, Window
from bench.peaks import Peaks, lookup
from bench.reference import qwen15_4b
from bench.trace import Summary

REPO = Path(__file__).resolve().parents[2]
SHAPE = qwen15_4b.shape({
    "num_hidden_layers": 2, "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 4, "intermediate_size": 128, "vocab_size": 100,
    "rope_theta": 1e4, "rms_norm_eps": 1e-6, "use_sliding_window": False,
    "sliding_window": 0, "torch_dtype": "bfloat16"})
SIZING = {"engine": {"slots": 4, "prefill_len": 8, "prefill_chunk": 4,
                     "page_size": 4, "cache_len": 16}}


class _Req:
    def __init__(self, n):
        self.output = [1] * n
        self.done = True


def _track(rid, due, submitted, stamps, prompt=6):
    g = generator.Req(rid, 0.0, np.ones(prompt, np.int32), len(stamps))
    return Track(g, _Req(len(stamps)), due, submitted, list(stamps),
                 stamps[-1] if stamps else None)


def _window(tracks, open_=10.0, close=20.0, c0=None, c1=None):
    zero = {"decode_steps": 0, "active_step_sum": 0, "prefill_tokens": 0,
            "cached_prefill_tokens": 0}
    return Window(open=open_, close=close, tracks=tracks,
                  counters_open=c0 or zero, counters_close=c1 or zero,
                  steps=0, compiles=0, late_max_s=0.0, late_mean_s=0.0,
                  statuses={t.gen.rid: "ok" for t in tracks})


def _run(win, trace=None):
    return stats.Run(window=win, shape=SHAPE, peaks=lookup("TPU v5 lite"),
                     sizing=SIZING, setup_s=7.5, trace=trace)


def _reader(name):
    return spec._reader(REPO, name)


def test_ttft_runs_from_the_due_time_not_the_submit():
    # Due at 11, submitted late at 12, first token at 13: 2 s, not 1 s.
    run = _run(_window([_track(0, 11.0, 12.0, [13.0, 13.5])]))
    assert stats.ttfts(run) == [2.0]


def test_percentiles_count_unfinished_requests_at_their_wait():
    done = [_track(i, 11.0, 11.0, [11.5]) for i in range(19)]
    starved = _track(99, 12.0, 12.0, [])          # no token by the close
    run = _run(_window(done + [starved]))
    waits = stats.ttfts(run)
    assert len(waits) == 20 and max(waits) == pytest.approx(8.0)
    assert _reader("ttft_p95_s")(run) == pytest.approx(
        np.percentile([0.5] * 19 + [8.0], 95))


def test_requests_due_outside_the_window_are_not_timed():
    run = _run(_window([_track(0, 9.0, 9.0, [9.5]),
                        _track(1, 21.0, 21.0, [21.5])]))
    assert stats.ttfts(run) == []


def test_tokens_and_gaps_inside_the_window_only():
    t = _track(0, 9.0, 9.0, [9.5, 10.5, 11.0, 21.0])
    run = _run(_window([t]))
    assert _reader("output_tok_s")(run) == pytest.approx(2 / 10.0)
    assert stats.token_gaps(run) == pytest.approx([1.0, 0.5])
    assert _reader("itl_p95_ms")(run) == pytest.approx(
        1e3 * np.percentile([1.0, 0.5], 95))


def test_counters_are_differences_across_the_window():
    c0 = {"decode_steps": 100, "active_step_sum": 300,
          "prefill_tokens": 1000, "cached_prefill_tokens": 500}
    c1 = {"decode_steps": 150, "active_step_sum": 400,
          "prefill_tokens": 1300, "cached_prefill_tokens": 600}
    run = _run(_window([], c0=c0, c1=c1))
    assert _reader("sched.occupancy_pct.batch")(run) == pytest.approx(
        100.0 * 100 / (50 * 4))
    assert _reader("prefix.hit_pct")(run) == pytest.approx(
        100.0 * 100 / 400)
    assert _reader("setup_s")(run) == 7.5


def test_readers_return_nothing_without_their_input():
    run = _run(_window([]))
    for name in ("device.idle_pct.serve", "matmul_roofline.serve",
                 "sched.occupancy_pct.batch", "prefix.hit_pct",
                 "ttft_p95_s", "itl_p95_ms", "step_mfu.serve"):
        assert _reader(name)(run) is None, name


def test_device_idle_from_the_trace_summary():
    summ = Summary(window_s=2.0, busy_s=1.5, matmul_s=1.0, device_ops=[],
                   idle_gaps=[], n_ops=3)
    run = _run(_window([]), trace=summ)
    assert _reader("device.idle_pct.batch")(run) == pytest.approx(25.0)


def test_roofline_of_a_traced_decode_step():
    """One traced decode step of two rows: the least time is the bytes
    of its projections and head over the bandwidth."""
    pk = Peaks(1e15, 1e15, 1e9, 1e9, 1e9)       # bandwidth-bound by far
    t = _track(0, 10.0, 10.0, [10.5, 11.5])     # token 1: kv 8 + 1
    u = _track(1, 10.0, 10.0, [10.5, 11.5])
    win = _window([t, u])
    win.traced = {"open": 11.0, "close": 12.0, "chunks": 0,
                  "counters_open": {"decode_steps": 5},
                  "counters_close": {"decode_steps": 6}}
    run = stats.Run(window=win, shape=SHAPE, peaks=pk, sizing=SIZING,
                    setup_s=0.0,
                    trace=Summary(1.0, 0.5, 0.25, [], [], 1))
    w = stats.traced_work(run)
    d, L, r = SHAPE.d_model, SHAPE.n_layers, 2
    gemm = L * sum(2 * (r * k + k * n + r * n) for n, k in work.gemms(SHAPE))
    head = 2 * (r * d + d * SHAPE.vocab) + 4 * r * SHAPE.vocab
    assert w.least_s == pytest.approx((gemm + head) / 1e9)
    assert _reader("matmul_roofline.serve")(run) == pytest.approx(
        100 * w.least_s / 0.25)


def test_step_mfu_counts_every_layer_of_the_useful_tokens():
    """A 6-token prompt whose first token and one more land in the window:
    the prompt's forward, one decode token over 8 positions, two heads."""
    run = _run(_window([_track(0, 10.0, 10.0, [10.5, 11.0])]))
    d, hd, L = SHAPE.d_model, SHAPE.head_dim, SHAPE.n_layers
    per_token = 2 * L * sum(n * k for n, k in work.gemms(SHAPE))
    attn = 4 * SHAPE.n_heads * hd * L
    flops = (7 * per_token + attn * (6 * 7 // 2 + 7)
             + 2 * 2 * d * SHAPE.vocab)
    assert stats.useful_flops(run) == pytest.approx(flops)
    assert _reader("step_mfu.serve")(run) == pytest.approx(
        100 * flops / (10.0 * 197e12))


def test_unknown_device_kind_is_an_error():
    assert lookup("TPU v5 lite").ici_bw == 200e9
    with pytest.raises(KeyError):
        lookup("TPU v9 imaginary")
