"""Collective operations inside the decode programs of a traced slice.

An op event is a collective when its HLO opcode or its instruction's name
holds one of ``trace.COLLECTIVES`` (``all-reduce``, ``all-reduce-start``,
``%all-gather.3``, a fusion XLA named after the collective it holds).  It
is inside a decode program when its midpoint lies in one of the chip's
``decode_l*`` program events.  Read by ``step.collective_share`` and
``step.collective_ops``.
"""

from __future__ import annotations

import bisect

from bench import program_spans as P
from bench import trace as T


def is_collective(name: str) -> bool:
    lhs = name.partition(" = ")[0].lstrip("%")
    op = T.opcode(name)
    return any(p in op or lhs.startswith(p) for p in T.COLLECTIVES)


def in_decode(ops, modules, lo: float, hi: float):
    """(collective op events, their summed device time, the decode
    programs' device time) inside the decode programs of one chip, cut to
    [lo, hi); None where no decode program runs there."""
    decode = T.merged(T.clip([e for e in modules if P.is_decode(e)], lo, hi))
    if not decode:
        return None
    starts = [a for a, _ in decode]
    count, ns = 0, 0.0
    for e in ops:
        if not is_collective(e.name):
            continue
        mid = e.start + e.dur / 2
        k = bisect.bisect_right(starts, mid) - 1
        if k >= 0 and mid < decode[k][1]:
            a, b = max(e.start, lo), min(e.end, hi)
            count += 1
            ns += max(0.0, b - a)
    return count, ns, P.length(decode)
