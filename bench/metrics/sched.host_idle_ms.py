"""Server / tier runtime: device idle time per traced decode step in which
no program executes and the host is inside one of the program's
``sched.*`` spans (admission, token bookkeeping, retirement) and in no
``tier.*`` span, in milliseconds."""

from bench import program_spans as P


def read(run):
    spans, n = P.host_spans(run), P.traced_step_count(run)
    if not spans or n <= 0:
        return None
    tr = run.trace
    chip = tr["chips"][0]
    ns = P.host_idle_ns(tr["ops"][chip], tr["modules"][chip], spans,
                        P.SCHED, P.TIER, tr["lo"], tr["hi"])
    return None if ns is None else ns / 1e6 / n
