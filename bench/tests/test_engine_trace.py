"""The engine's spans and programs in a trace: the reductions on synthetic
intervals whose answers are known; the readers on a trace recorded on a TPU
v5e (the tiny chat cell through the whole harness, ``record_trace.py``,
kept as ``chip_trace_engine.*``), and on the one recorded before the engine
had spans or program names, where each reads nothing and raises nothing."""
import gzip
import json
import shutil
import types
from pathlib import Path

import pytest

from bench import engine_trace as et
from bench import spec, trace
from bench.trace import Op, Span

DATA = Path(__file__).resolve().parent / "data"
ENGINE = "chip_trace_engine.xplane.pb.gz"
READERS = ("decode.device_ms.serve", "prefill.chunk_device_ms.serve",
           "engine.host_gap_ms.serve")


def _synthetic():
    """Window [0, 10]; the device busy in [0,1], [3,4], [6,7], [9,10], so
    idle in [1,3], [4,6], [7,9]; two steps with children."""
    ops = [Op(s, s + 1.0, "op", "loop fusion") for s in (0.0, 3.0, 6.0, 9.0)]
    spans = [Span(0.5, 5.0, "step"), Span(1.5, 2.0, "decode_launch"),
             Span(2.5, 3.5, "device_wait"), Span(5.5, 9.5, "step"),
             Span(6.5, 8.0, "admit")]
    modules = [Span(0.0, 1.0, et.DECODE), Span(3.0, 4.0, et.DECODE),
               Span(9.0, 10.5, et.DECODE),           # ends after the window
               Span(6.0, 6.5, et.PREFILL_CHUNK),
               Span(6.5, 7.0, et.PREFILL_CHUNK),
               Span(7.2, 7.3, et.PREFILL_CHUNK),
               Span(4.0, 9.0, "jit_sample_token")]
    return ops, spans, modules


def test_idle_goes_to_the_innermost_span_and_none_outside_every_span():
    ops, spans, modules = _synthetic()
    e = et.reduce(ops, spans, modules, 0.0, 10.0)
    assert e.steps == 2
    assert e.idle_ms == pytest.approx(6000.0)
    # Per step: [1,1.5] [2,2.5] [4,5] [5.5,6] [8,9] under step alone,
    # [5,5.5] between the steps under no span.
    assert e.idle_by_span == pytest.approx({
        "step": 1750.0, "decode_launch": 250.0, "device_wait": 250.0,
        "admit": 500.0, et.NONE: 250.0})
    assert sum(e.idle_by_span.values()) * e.steps == pytest.approx(e.idle_ms)
    # Inside a step, whatever child covers it: all idle but [5, 5.5].
    assert e.host_gap_ms == pytest.approx(2750.0)


def test_module_medians_count_only_runs_inside_the_window():
    ops, spans, modules = _synthetic()
    e = et.reduce(ops, spans, modules, 0.0, 10.0)
    assert e.decode_ms == pytest.approx(1000.0)
    assert e.chunk_ms == pytest.approx(500.0)
    assert et.module_median_s(modules, "jit_absent", 0.0, 10.0) is None


def test_pieces_cover_the_window_and_clip_spans_that_cross_it():
    pieces = et.innermost([Span(-1.0, 2.0, "step"), Span(1.0, 1.5, "sample"),
                           Span(9.0, 12.0, "step")], 0.0, 10.0)
    assert pieces == [(0.0, 1.0, "step"), (1.0, 1.5, "sample"),
                      (1.5, 2.0, "step"), (2.0, 9.0, et.NONE),
                      (9.0, 10.0, "step")]
    assert et.innermost([], 0.0, 4.0) == [(0.0, 4.0, et.NONE)]
    assert et.split([(0.5, 3.0)], et.innermost([], 0.0, 4.0)) == \
        {et.NONE: 2.5}


def test_a_program_without_engine_spans_reads_none():
    ops, _, modules = _synthetic()
    e = et.reduce(ops, [], [Span(0.0, 1.0, "jit__lambda")], 0.0, 10.0)
    assert (e.steps, e.decode_ms, e.chunk_ms, e.host_gap_ms,
            e.idle_by_span) == (0, None, None, None, {})


def _traces(tmp_path, fixture):
    """A trace directory laid out as ``bench/run.py`` leaves it."""
    d = tmp_path / "cell" / "plugins" / "profile" / "t"
    d.mkdir(parents=True)
    with gzip.open(DATA / fixture, "rb") as f, \
            open(d / "host.xplane.pb", "wb") as g:
        shutil.copyfileobj(f, g)
    return tmp_path


def _run(fixture):
    ops, spans, window = trace.load(DATA / fixture)
    return types.SimpleNamespace(trace=trace.summarize(
        ops, spans, window.start, window.end))


def test_readers_read_nothing_in_a_trace_of_the_older_program(
        tmp_path, monkeypatch):
    old = "chip_trace.xplane.pb.gz"
    monkeypatch.setattr(et, "TRACES", _traces(tmp_path, old))
    spans, modules = et.load(DATA / old)
    assert spans == []
    assert any(m.name == "jit__lambda" for m in modules)
    run = _run(old)
    e = et.of_run(run)
    assert e is not None and e.steps == 0
    for name in READERS:
        assert spec._reader(spec.REPO, name)(run) is None
    # A run whose window no trace there has finds nothing.
    run.trace.window_s += 1.0
    assert et.of_run(run) is None
    assert et.of_run(types.SimpleNamespace(trace=None)) is None


def test_the_chip_trace_has_engine_spans_named_programs_and_readings(
        tmp_path, monkeypatch):
    spans, modules = et.load(DATA / ENGINE)
    programs = {m.name for m in modules}
    assert not any("lambda" in p for p in programs)
    assert {et.DECODE, et.PREFILL_CHUNK} <= programs
    assert {"step", "decode_launch", "device_wait", "observe", "sample",
            "evict"} <= {sp.name for sp in spans}
    monkeypatch.setattr(et, "TRACES", _traces(tmp_path, ENGINE))
    run = _run(ENGINE)
    for name in READERS:
        assert spec._reader(spec.REPO, name)(run) > 0
    with open(DATA / "chip_trace_engine.json") as f:
        device = json.load(f)["device"]
    assert run.trace.window_s == pytest.approx(device["window_s"])
    e = et.of_run(run)
    idle_ms = 1e3 * (device["window_s"] - device["busy_s"])
    assert sum(e.idle_by_span.values()) * e.steps == \
        pytest.approx(idle_ms, rel=0.01)
    assert e.idle_ms == pytest.approx(idle_ms, rel=0.01)
