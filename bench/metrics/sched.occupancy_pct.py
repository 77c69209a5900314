"""Share of decode-batch rows that carried a request, across the window:
rows used over decode steps times slots (the scheduler's counts)."""
from bench import stats


def read(run: stats.Run):
    steps = stats.delta(run, "decode_steps")
    if not steps:
        return None
    used = stats.delta(run, "active_step_sum")
    return 100.0 * used / (steps * run.sizing["engine"]["slots"])
