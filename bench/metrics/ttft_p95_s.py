"""95th percentile of time to first token over every request due in the
window, timed from when it was due; a request still without a token at the
close counts the time it had waited."""
from bench import stats


def read(run: stats.Run):
    return stats.percentile(stats.ttfts(run), 95)
