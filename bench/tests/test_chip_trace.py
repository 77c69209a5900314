"""The trace reduction on a trace recorded on a TPU v5e: the tiny chat cell
run through the whole harness with ``--trace 1`` (``record_trace.py``).
``chip_trace.json`` holds what the harness read from it on the chip."""
import collections
import json
from pathlib import Path

import pytest

from bench import trace

DATA = Path(__file__).resolve().parent / "data"


@pytest.fixture(scope="module")
def recorded():
    ops, spans, window = trace.load(DATA / "chip_trace.xplane.pb.gz")
    with open(DATA / "chip_trace.json") as f:
        return ops, spans, window, json.load(f)


def test_load_finds_device_ops_harness_spans_and_the_window(recorded):
    ops, spans, window, _ = recorded
    cats = collections.Counter(o.category for o in ops)
    # The XLA route's products are convolution fusions; no event lacks a
    # category.
    assert cats["convolution fusion"] > 0 and "unknown" not in cats
    assert {"bench.step", "bench.submit", "bench.deliver"} <= \
        {sp.name for sp in spans}
    assert window is not None and window.end > window.start
    assert all(window.start - 1 < sp.start for sp in spans)


def test_summary_matches_what_the_harness_read_on_the_chip(recorded):
    ops, spans, window, want = recorded
    s = trace.summarize(ops, spans, window.start, window.end)
    assert s.window_s == pytest.approx(want["device"]["window_s"])
    assert s.busy_s == pytest.approx(want["device"]["busy_s"])
    assert s.matmul_s == pytest.approx(want["matmul_s"])
    assert s.n_ops == want["ops"]
    assert 0 < s.matmul_s < s.busy_s < s.window_s
    assert s.idle_gaps == want["breakdown"]["idle_gaps"]
    assert s.device_ops == want["breakdown"]["device_ops"]
