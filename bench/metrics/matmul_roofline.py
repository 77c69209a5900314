"""Least time the traced window's weight-bearing matrix products need at
the chip's peaks (bench.work: projections, MLP, head), over the device time
of the trace's product operations (XLA convolutions and Pallas custom
calls)."""
from bench import stats


def read(run: stats.Run):
    t = run.trace
    w = stats.traced_work(run)
    if t is None or w is None or t.matmul_s <= 0 or w.least_s <= 0:
        return None
    return 100.0 * w.least_s / t.matmul_s
