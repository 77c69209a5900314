"""Share of prompt-window tokens the prefix cache served instead of
prefilling them, across the window (the scheduler's cumulative counts)."""
from bench import stats


def read(run: stats.Run):
    hit = stats.delta(run, "cached_prefill_tokens")
    asked = hit + stats.delta(run, "prefill_tokens")
    return 100.0 * hit / asked if asked else None
