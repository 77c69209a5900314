"""Median device time of the prefill chunk program
(``jit_serve_prefill_chunk``, every chunk index) over its runs in the
traced window, in milliseconds (bench.engine_trace)."""
from bench import engine_trace, stats


def read(run: stats.Run):
    e = engine_trace.of_run(run)
    return None if e is None else e.chunk_ms
