"""Model step: device time of the entry tier's decode programs
(``decode_l0_*``) in the traced slice per traced decode step, in
milliseconds."""

from bench import program_spans as P


def read(run):
    tr, n = run.trace, P.traced_step_count(run)
    if not tr or n <= 0:
        return None
    ns = P.module_ns(tr["modules"][tr["chips"][0]], P.is_edge,
                     tr["lo"], tr["hi"])
    return None if ns is None else ns / 1e6 / n
