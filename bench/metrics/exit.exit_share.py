"""Exit heads: share of the window's emitted tokens that left at an exit
head, in percent."""

from bench.accounting import window_steps


def read(run):
    steps = set(window_steps(run))
    toks = [t for t in run.tokens if t[0] in steps]
    if not toks:
        return None
    return 100.0 * sum(1 for t in toks if t[2]) / len(toks)
