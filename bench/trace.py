"""From a profiler trace to device busy time, matmul time and idle gaps.

The reduction works on plain intervals, so it can be checked on a
synthetic trace whose answers are known; :func:`load` turns a recorded
``.xplane.pb`` into those intervals:

- device operations: the events of the first TPU plane's ``XLA Ops``
  line, each with its HLO category;
- host spans: the events of the harness's own ``TraceAnnotation`` spans
  (names starting with ``bench.``), on the same clock.

Busy time is the union of the device operations' intervals inside the
traced window; an idle gap is a stretch of the window with no operation,
named after the harness span that covers most of it. Matmul time is the
summed time of the operations whose HLO category is a convolution (XLA's
dots) or a custom call (Pallas kernels), clipped to the window.
"""
from __future__ import annotations

import collections
import dataclasses
import gzip
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

MATMUL_CATEGORIES = ("convolution", "convolution fusion", "custom-call")
WINDOW_SPAN = "bench.traced"


@dataclasses.dataclass(frozen=True)
class Op:
    start: float      # seconds, trace clock
    end: float
    name: str
    category: str


@dataclasses.dataclass(frozen=True)
class Span:
    start: float
    end: float
    name: str


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float
    matmul_s: float
    device_ops: List[Tuple[str, float]]    # most time first, at most 10
    idle_gaps: List[Tuple[str, float]]     # longest first, at most 10
    n_ops: int


def union_length(intervals: Iterable[Tuple[float, float]]) -> float:
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def idle_intervals(ops: Sequence[Op], lo: float, hi: float
                   ) -> List[Tuple[float, float]]:
    """Stretches of [lo, hi] in which no operation runs."""
    out, t = [], lo
    for op in sorted(ops, key=lambda o: o.start):
        if op.end <= t:
            continue
        if op.start > t:
            out.append((t, min(op.start, hi)))
        t = max(t, op.end)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


def attribute(gap: Tuple[float, float], spans: Sequence[Span]) -> str:
    """The name of the host span that covers most of ``gap``."""
    best, best_len = "no harness span", 0.0
    for sp in spans:
        cover = min(gap[1], sp.end) - max(gap[0], sp.start)
        if cover > best_len:
            best, best_len = sp.name, cover
    return best


def summarize(ops: Sequence[Op], spans: Sequence[Span], lo: float,
              hi: float) -> Summary:
    """Reduce the operations and host spans inside [lo, hi]."""
    inside = [Op(max(o.start, lo), min(o.end, hi), o.name, o.category)
              for o in ops if o.end > lo and o.start < hi]
    busy = union_length((o.start, o.end) for o in inside)
    matmul = sum(o.end - o.start for o in inside
                 if o.category in MATMUL_CATEGORIES)
    per_name: Dict[str, float] = collections.defaultdict(float)
    for o in inside:
        per_name[f"{o.category}: {o.name}"] += o.end - o.start
    top_ops = sorted(per_name.items(), key=lambda kv: -kv[1])[:10]
    gaps = sorted(idle_intervals(inside, lo, hi),
                  key=lambda g: g[0] - g[1])[:10]
    return Summary(window_s=hi - lo, busy_s=busy, matmul_s=matmul,
                   device_ops=[[n, t] for n, t in top_ops],
                   idle_gaps=[[attribute(g, spans), g[1] - g[0]]
                              for g in gaps],
                   n_ops=len(inside))


# -- reading a recorded trace ---------------------------------------------------


def find_xplane(log_dir: Path) -> Path:
    found = sorted(Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


# The file is an ``XSpace`` protocol buffer (tsl/profiler/protobuf/
# xplane.proto), read here field by field: an operation's HLO category is a
# stat of its event *metadata*, which ``jax.profiler.ProfileData`` does not
# expose. Field numbers of the messages read:
#   XSpace.planes 1; XPlane: name 2, lines 3, event_metadata 4 (map),
#   stat_metadata 5 (map); XLine: name 2, timestamp_ns 3, events 4;
#   XEvent: metadata_id 1, offset_ps 2, duration_ps 3, stats 4;
#   XEventMetadata: name 2, stats 5; XStatMetadata: name 2;
#   XStat: metadata_id 1, str_value 5, ref_value 7 (a stat metadata id).


def _varint(b: bytes, i: int) -> Tuple[int, int]:
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return x, i


def _fields(b: bytes) -> Dict[int, list]:
    """Each field number of one message with its values in order
    (varints as ints, length-delimited fields as bytes)."""
    out: Dict[int, list] = collections.defaultdict(list)
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            size, i = _varint(b, i)
            v, i = b[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v, i = b[i:i + size], i + size
        else:
            raise ValueError(f"unsupported wire type {wire}")
        out[key >> 3].append(v)
    return out


def _first(f: Dict[int, list], n: int, default=None):
    return f[n][0] if f.get(n) else default


def _text(f: Dict[int, list], n: int) -> str:
    return bytes(_first(f, n, b"")).decode("utf-8", "replace")


def _map(f: Dict[int, list], n: int) -> Dict[int, Dict[int, list]]:
    """A ``map<int64, message>`` field: key 1, value 2."""
    out = {}
    for entry in f.get(n, []):
        e = _fields(entry)
        out[_first(e, 1, 0)] = _fields(_first(e, 2, b""))
    return out


def _stats(raw: list, stat_names: Dict[int, str]) -> Dict[str, str]:
    out = {}
    for s in raw:
        f = _fields(s)
        name = stat_names.get(_first(f, 1, 0), "")
        if f.get(5):
            out[name] = _text(f, 5)
        elif f.get(7):
            out[name] = stat_names.get(_first(f, 7), "")
    return out


def _events(line: Dict[int, list]):
    """(metadata id, start s, end s, raw stats) of each event of a line."""
    t0 = _first(line, 3, 0) * 1e-9
    for raw in line.get(4, []):
        e = _fields(raw)
        s = t0 + _first(e, 2, 0) * 1e-12
        yield _first(e, 1, 0), s, s + _first(e, 3, 0) * 1e-12, e.get(4, [])


def load(path: Path, device: str = "/device:TPU:0"
         ) -> Tuple[List[Op], List[Span], Optional[Span]]:
    """Device operations of ``device``, the harness's host spans, and the
    traced window (the ``bench.traced`` span) from an ``.xplane.pb``
    (optionally gzipped)."""
    path = Path(path)
    opener = gzip.open if path.suffix == ".gz" else open
    with opener(path, "rb") as f:
        space = _fields(f.read())
    ops: List[Op] = []
    spans: List[Span] = []
    for raw_plane in space.get(1, []):
        plane = _fields(raw_plane)
        name = _text(plane, 2)
        if name != device and not name.startswith("/host:"):
            continue
        stat_names = {k: _text(v, 2) for k, v in _map(plane, 5).items()}
        meta = _map(plane, 4)
        names = {k: _text(v, 2) for k, v in meta.items()}
        for raw_line in plane.get(3, []):
            line = _fields(raw_line)
            if name == device:
                if _text(line, 2) != "XLA Ops":
                    continue
                for mid, s, e, st in _events(line):
                    cat = _stats(st, stat_names).get("hlo_category") or \
                        _stats(meta.get(mid, {}).get(5, []), stat_names).get(
                            "hlo_category", "unknown")
                    ops.append(Op(s, e, names.get(mid, ""), cat))
            else:
                for mid, s, e, _ in _events(line):
                    if names.get(mid, "").startswith("bench."):
                        spans.append(Span(s, e, names[mid]))
    window = next((sp for sp in spans if sp.name == WINDOW_SPAN), None)
    spans = [sp for sp in spans if sp.name != WINDOW_SPAN]
    return ops, spans, window
