"""Share of the chip's bf16 peak that the window's useful work used: the
operations of the unpadded prompts finished and the output tokens
delivered in the window (bench.stats.useful_flops), over the window's
seconds times the peak."""
from bench import stats


def read(run: stats.Run):
    f = stats.useful_flops(run)
    if f <= 0:
        return None
    return 100.0 * f / (run.window.seconds * run.peaks.bf16_flops)
