"""Server / tier runtime: mean host time inside ``PartitionedServer.step``
(which ends in the step's one sync) over the window's decode steps."""

from bench.accounting import window_steps


def read(run):
    steps = window_steps(run)
    if not steps:
        return None
    return 1e3 * sum(run.steps[i].server_s for i in steps) / len(steps)
