"""Rows that one read of a held expert's weights serves, across the
window: the engine's assignments of a computed row to a held expert over
its held experts read (an expert with at least one such row in one layer
call), both summed over every expert layer and call
(``moe_assignments`` / ``moe_experts_read``). Nothing where the engine
reports neither."""
from bench import stats


def read(run: stats.Run):
    counts = stats.expert_counts(run)
    if counts is None or not counts[1]:
        return None
    assignments, experts_read = counts
    return assignments / experts_read
