"""Compaction: true survivors over the rows of the bucket the downstream
segment ran, summed over the window's steps, in percent."""

from bench.accounting import window_steps


def read(run):
    hops = [h for i in window_steps(run) for h in run.steps[i].hops]
    rows = sum(b for _, b in hops)
    return 100.0 * sum(s for s, _ in hops) / rows if rows else None
