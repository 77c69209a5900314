"""Output tokens delivered in the window over the window's seconds."""
from bench import stats


def read(run: stats.Run):
    w = run.window
    return sum(1 for _ in stats.stamps_in(run, w.open, w.close)) / w.seconds
