"""95th percentile of the gaps between consecutive output tokens of a
request, over every token delivered in the window, in milliseconds."""
from bench import stats


def read(run: stats.Run):
    p = stats.percentile(stats.token_gaps(run), 95)
    return None if p is None else 1e3 * p
