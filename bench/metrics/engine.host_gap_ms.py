"""Device-idle time inside the engine's ``step`` spans over the number of
steps in the traced window, in milliseconds: the host work per step that
the device waits for (bench.engine_trace)."""
from bench import engine_trace, stats


def read(run: stats.Run):
    e = engine_trace.of_run(run)
    return None if e is None else e.host_gap_ms
