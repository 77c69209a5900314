"""Median device time of the decode step program (``jit_serve_decode``
on the device's XLA Modules line) over its runs in the traced window, in
milliseconds (bench.engine_trace)."""
from bench import engine_trace, stats


def read(run: stats.Run):
    e = engine_trace.of_run(run)
    return None if e is None else e.decode_ms
