"""Server / tier runtime: the executor's host syncs per decode step over the
window, overflow re-runs included (one is the runtime's contract)."""

from bench.accounting import window_steps


def read(run):
    steps = window_steps(run)
    if not steps:
        return None
    return sum(run.steps[i].syncs for i in steps) / len(steps)
