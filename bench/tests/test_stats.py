"""The readers' arithmetic on a window whose answers are known."""
import dataclasses
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


# bench.work's counts at qwen1.5-4b's shape on a v5e, recorded before
# configurations brought their own layers: (flops, bytes, least_s).
QWEN_DECODE = {
    (100, 1234): (8788297646079.997, 717762999296.0016, 0.8763894985299111),
    (7, 7): (49852579840.0, 49884251648.0, 0.06090873217093999),
    (3, 50): (356089855999.99945, 21591618560.000008, 0.026363392625152644),
    (0, 0): (0.0, 0.0, 0.0)}
QWEN_CHUNK = {128: (812795166720.0, 7623105024.0, 0.009307820542124567),
              1: (7121797120.0, 7126321664.0, 0.008701247452991428),
              7: (45185105920.0, 7149791744.0, 0.00872990444932846)}
QWEN_ATTENTION = {1: (409600.0, 409600.0),
                  300: (122880000.0, 18493440000.0),
                  512: (209715200.0, 53791948800.0)}


def test_dense_work_counts_are_the_recorded_ones():
    s = _real_shape("qwen15_4b")
    pk = lookup("TPU v5 lite")
    for (steps, tokens), want in QWEN_DECODE.items():
        w = work.decode_steps(s, steps, tokens, pk.bf16_flops, pk.hbm_bw)
        assert (w.flops, w.bytes, w.least_s) == want
    for size, want in QWEN_CHUNK.items():
        w = work.chunk(s, size, pk.bf16_flops, pk.hbm_bw)
        assert (w.flops, w.bytes, w.least_s) == want
    assert work.token_flops(s) == 6343884800.0
    assert work.head_flops(s) == 777912320.0
    for kv, want in QWEN_ATTENTION.items():
        assert (work.attention_flops(s, kv),
                work.prompt_attention_flops(s, kv)) == want
    sc = _real_shape("starcoder2_7b")           # every layer windowed
    w = work.decode_steps(sc, 100, 1234, pk.bf16_flops, pk.hbm_bw)
    assert (w.flops, w.bytes, w.least_s) == (
        9130060283904.0, 743081019392.0002, 0.9073028319804631)
    assert (work.attention_flops(sc, 5000),
            work.prompt_attention_flops(sc, 5000)) == (1207959552.0,
                                                       3566500577280.0)


def _real_shape(name):
    import json
    from bench.spec import load_module
    c = json.loads((REPO / f"bench/configs/{name}.json").read_text())
    return load_module(REPO / "bench/reference" / f"{name}.py").shape(c)


# Two expert layers of 8 experts of 32, top-2, the first windowed; a
# share holds 2 of the 8.
MOE = dataclasses.replace(
    SHAPE, kinds=(("local", "moe"), ("attn", "moe")), window=4,
    n_experts=8, top_k=2, d_ff_expert=32)
SHARE = dataclasses.replace(MOE, experts_here=2)
EXPERT = [(32, 64), (32, 64), (64, 32)]


def test_expert_layers_count_router_and_assignments():
    rows = sum(n * k for n, k in work.gemms(SHAPE)[:4]) + 8 * 64
    assert work.gemms(MOE, 1) == work.gemms(SHAPE)[:4] + [(8, 64)]
    assert work.token_flops(MOE, experts=False) == 2.0 * 2 * rows
    # Every expert held: k assignments per row.
    assert work.token_flops(MOE) == 2.0 * 2 * (
        rows + 2 * sum(n * k for n, k in EXPERT))
    # A share, and no counters: no expert work, only the router.
    assert work.token_flops(SHARE) == work.token_flops(MOE, experts=False)
    pk, bw = 1e12, 1e9
    for s in (MOE, SHARE):
        assert work.decode_steps(s, 3, 12, pk, bw, experts=False) == \
            work.decode_steps(SHARE, 3, 12, pk, bw)
    # Attention over each layer's own window.
    assert work.attention_flops(MOE, 10) == 4.0 * 64 * (4 + 10)
    assert work.prompt_attention_flops(MOE, 6) == 4.0 * 64 * (
        (4 * 5 // 2 + 2 * 4) + 6 * 7 // 2)


def _least(products, per_expert, pk, bw):
    """Least time of a perfect implementation: each expert read once,
    one call per expert and product."""
    return sum(max(2.0 * a * n * k / pk, 2 * (a * k + k * n + a * n) / bw)
               for n, k in products for a in per_expert if a)


@pytest.mark.parametrize("pk,bw", [(1e15, 1e9), (1e9, 1e15), (1e12, 1e11)])
def test_counted_expert_time_never_exceeds_what_routing_needs(pk, bw):
    """Whatever the routing, the certain count (k assignments per row, k
    experts read per call) and the count from the counters are at most
    the least time that routing needs."""
    rng = np.random.default_rng(0)
    for rows in (1, 3, 16, 128):
        certain = work.chunk(MOE, rows, pk, bw) + \
            work.chunk(MOE, rows, pk, bw, experts=False) * -1
        for _ in range(20):
            need, a_total, e_total = 0.0, 0, 0
            for _layer in range(2):
                picks = np.stack([rng.choice(8, 2, replace=False)
                                  for _ in range(rows)])
                per = np.bincount(picks.ravel(), minlength=8)
                need += _least(EXPERT, per, pk, bw)
                a_total += int(per.sum())
                e_total += int((per > 0).sum())
            assert certain.least_s <= need * (1 + 1e-12)
            assert certain.flops == pytest.approx(
                sum(2.0 * a_total * n * k for n, k in EXPERT))
            counted = work.routed(MOE, a_total, e_total, pk, bw)
            assert counted.least_s <= need * (1 + 1e-12)
            assert counted.flops == certain.flops


def test_the_engine_counters_replace_the_certain_count():
    pk = Peaks(1e12, 1e12, 1e9, 1e9, 1e9)
    t = _track(0, 10.0, 10.0, [10.5, 11.5])
    win = _window([t])
    win.traced = {"open": 11.0, "close": 12.0, "chunks": 1,
                  "counters_open": {"decode_steps": 5},
                  "counters_close": {"decode_steps": 6}}

    def run(shape):
        return stats.Run(window=win, shape=shape, peaks=pk, sizing=SIZING,
                         setup_s=0.0, trace=Summary(1.0, 0.5, 0.25, [], [],
                                                    1))
    assert stats.expert_counts(run(SHARE), traced=True) is None
    plain = stats.traced_work(run(SHARE))
    assert plain == work.decode_steps(SHARE, 1, 1, pk.bf16_flops, 1e9) \
        + work.chunk(SHARE, 4, pk.bf16_flops, 1e9)
    win.traced["counters_open"].update(moe_assignments=10,
                                       moe_experts_read=3)
    win.traced["counters_close"].update(moe_assignments=22,
                                        moe_experts_read=7)
    assert stats.expert_counts(run(SHARE), traced=True) == (12, 4)
    assert stats.traced_work(run(SHARE)) == plain + work.routed(
        SHARE, 12, 4, pk.bf16_flops, 1e9)
    # The full set's certain count gives way to the counters.
    assert stats.traced_work(run(MOE)) == plain + work.routed(
        MOE, 12, 4, pk.bf16_flops, 1e9)


def test_useful_work_takes_the_experts_from_the_counters():
    zero = {"decode_steps": 0, "active_step_sum": 0, "prefill_tokens": 0,
            "cached_prefill_tokens": 0}
    t = _track(0, 10.0, 10.0, [10.5, 11.0])

    def useful(shape, c0=zero, c1=zero):
        return stats.useful_flops(stats.Run(
            window=_window([t], c0=dict(c0), c1=dict(c1)), shape=shape,
            peaks=lookup("TPU v5 lite"), sizing=SIZING, setup_s=0.0))
    expert = 2.0 * sum(n * k for n, k in EXPERT)
    # Without counters the full set counts k per token, a share nothing.
    assert useful(MOE) - useful(SHARE) == pytest.approx(
        2 * 2 * 7 * expert)
    c1 = dict(zero, moe_assignments=30, moe_experts_read=5)
    c0 = dict(zero, moe_assignments=0, moe_experts_read=0)
    for s in (MOE, SHARE):
        assert useful(s, c0, c1) == pytest.approx(useful(SHARE)
                                                  + 30 * expert)
