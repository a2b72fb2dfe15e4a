"""Model step: device time of the collective operations that run inside
the decode programs (``bench.collectives``) over those programs' device
time in the traced slice, in percent; the mean over the cell's chips.
What the tensor-parallel segments pay for their all-reduces and
re-layouts."""

from bench.collectives import in_decode


def read(run):
    tr = run.trace
    if not tr:
        return None
    shares = []
    for chip in tr["chips"]:
        got = in_decode(tr["ops"][chip], tr["modules"].get(chip, []),
                        tr["lo"], tr["hi"])
        if got is not None and got[2] > 0:
            shares.append(100.0 * got[1] / got[2])
    return sum(shares) / len(shares) if shares else None
