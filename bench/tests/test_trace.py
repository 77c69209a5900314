"""The trace reduction on a synthetic trace whose answers are known."""
import pytest

from bench.trace import Op, Span, idle_intervals, summarize, union_length


def test_union_merges_overlaps_and_keeps_gaps():
    assert union_length([(0, 2), (1, 3), (5, 6), (5.5, 5.7)]) == 4
    assert union_length([]) == 0


def _synthetic():
    ops = [
        Op(0.0, 1.0, "fusion.1", "convolution fusion"),
        Op(0.5, 1.5, "copy.2", "data formatting"),        # overlaps
        Op(3.0, 4.0, "fusion.1", "convolution fusion"),
        Op(4.0, 4.5, "mte_gemm", "custom-call"),
        Op(9.0, 12.0, "loop_fusion.3", "loop fusion"),     # crosses hi
        Op(-2.0, -1.0, "before", "convolution"),           # outside
    ]
    spans = [Span(-1.0, 2.0, "bench.step"), Span(1.2, 2.9, "bench.deliver"),
             Span(4.5, 9.5, "bench.wait")]
    return ops, spans


def test_busy_matmul_and_idle_of_a_known_trace():
    ops, spans = _synthetic()
    s = summarize(ops, spans, 0.0, 10.0)
    assert s.window_s == 10.0
    assert s.busy_s == pytest.approx(1.5 + 1.5 + 1.0)       # [0,1.5] [3,4.5] [9,10]
    assert s.matmul_s == pytest.approx(2.0 + 0.5)
    assert s.n_ops == 5
    assert s.device_ops[0] == ["convolution fusion: fusion.1", 2.0]
    assert idle_intervals([o for o in ops if o.start >= 0], 0.0, 10.0) == [
        (1.5, 3.0), (4.5, 9.0)]
    # Longest gap first, each named by the span covering most of it.
    assert s.idle_gaps == [["bench.wait", pytest.approx(4.5)],
                           ["bench.deliver", pytest.approx(1.5)]]


def test_a_gap_no_span_covers():
    s = summarize([Op(0.0, 1.0, "a", "convolution")], [], 0.0, 2.0)
    assert s.idle_gaps == [["no harness span", 1.0]]
    assert s.busy_s == 1.0 and s.matmul_s == 1.0


# An XSpace as the profiler writes it: the device's "XLA Ops" line, whose
# HLO category is a stat of each operation's metadata (once as a string,
# once as a reference to a stat name), and a host plane with the harness's
# spans. Times: line at 1 s, offsets and durations in picoseconds.
XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000000000
    events { metadata_id: 7 offset_ps: 0 duration_ps: 2000000000000 }
    events { metadata_id: 8 offset_ps: 3000000000000 duration_ps: 1000000000000 }
    events { metadata_id: 9 offset_ps: 4000000000000 duration_ps: 500000000000 }
  }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000000000
    events { metadata_id: 7 offset_ps: 0 duration_ps: 9000000000000 }
  }
  event_metadata { key: 7 value { id: 7 name: "fusion.1"
    stats { metadata_id: 2 str_value: "convolution fusion" } } }
  event_metadata { key: 8 value { id: 8 name: "copy.2"
    stats { metadata_id: 2 ref_value: 3 } } }
  event_metadata { key: 9 value { id: 9 name: "mystery" } }
  stat_metadata { key: 2 value { id: 2 name: "hlo_category" } }
  stat_metadata { key: 3 value { id: 3 name: "data formatting" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000000000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 6000000000000 }
    events { metadata_id: 2 offset_ps: 2000000000000 duration_ps: 1000000000000 }
    events { metadata_id: 3 offset_ps: 0 duration_ps: 1000000000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.traced" } }
  event_metadata { key: 2 value { id: 2 name: "bench.wait" } }
  event_metadata { key: 3 value { id: 3 name: "PjitFunction" } }
}
"""


@pytest.mark.parametrize("gz", [False, True])
def test_load_reads_categories_from_the_op_metadata(tmp_path, gz):
    import gzip

    from jax.profiler import ProfileData

    from bench.trace import load
    raw = ProfileData.text_proto_to_serialized_xspace(XSPACE)
    path = tmp_path / ("t.xplane.pb.gz" if gz else "t.xplane.pb")
    path.write_bytes(gzip.compress(raw) if gz else raw)
    ops, spans, window = load(path)
    assert ops == [Op(1.0, 3.0, "fusion.1", "convolution fusion"),
                   Op(4.0, 5.0, "copy.2", "data formatting"),
                   Op(5.0, 5.5, "mystery", "unknown")]
    assert spans == [Span(3.0, 4.0, "bench.wait")]
    assert window == Span(1.0, 7.0, "bench.traced")
    s = summarize(ops, spans, window.start, window.end)
    assert s.busy_s == pytest.approx(3.5) and s.matmul_s == pytest.approx(2)
    assert s.idle_gaps == [["no harness span", pytest.approx(1.5)],
                           ["bench.wait", pytest.approx(1.0)]]
