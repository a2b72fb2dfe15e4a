"""Exit heads and compaction: device time of the decode programs that an
overflow re-run dispatched (those that start after the first ``tier.sync``
of their ``tier.step`` has ended) over the device time of every decode
program in the traced slice, in percent."""

from bench import program_spans as P


def read(run):
    spans = P.host_spans(run)
    if not spans:
        return None
    tr = run.trace
    got = P.retry_ns(tr["modules"][tr["chips"][0]], spans, tr["lo"],
                     tr["hi"])
    if got is None or got[1] <= 0:
        return None
    return 100.0 * got[0] / got[1]
