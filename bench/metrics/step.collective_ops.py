"""Model step: collective op events inside the decode programs
(``bench.collectives``) on the first chip, per traced decode step.  About
two all-reduces a layer (attention output, MLP down projection) plus the
vocabulary-sharded heads' reductions; a cache all-gather or an extra
re-layout shows here first."""

from bench import program_spans as P
from bench.collectives import in_decode


def read(run):
    tr, n = run.trace, P.traced_step_count(run)
    if not tr or n <= 0:
        return None
    chip = tr["chips"][0]
    got = in_decode(tr["ops"][chip], tr["modules"].get(chip, []),
                    tr["lo"], tr["hi"])
    return None if got is None else got[0] / n
