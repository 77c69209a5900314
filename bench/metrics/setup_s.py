"""Seconds from the harness's start to the window's: weights, compiling or
loading programs, warm-up and, for a backlog, the pre-roll."""
from bench import stats


def read(run: stats.Run):
    return run.setup_s
