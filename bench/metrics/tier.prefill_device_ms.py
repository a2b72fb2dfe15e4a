"""Model step: device time of the admission prefill programs
(``prefill_p*_n*``) in the traced slice per request the traced steps
admitted, in milliseconds."""

from bench import program_spans as P
from bench.accounting import traced_steps


def read(run):
    tr = run.trace
    admitted = sum(len(run.steps[i].admitted) for i in traced_steps(run))
    if not tr or admitted <= 0:
        return None
    ns = P.module_ns(tr["modules"][tr["chips"][0]], P.is_prefill,
                     tr["lo"], tr["hi"])
    return None if ns is None else ns / 1e6 / admitted
