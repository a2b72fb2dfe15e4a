"""Model step: the least bytes the traced decode steps had to move (each
executed segment's weights once, the K/V of the positions live rows attend
to, the heads' unembedding) over the device time of the programs other
than admission prefills times the chip's HBM bandwidth, in percent.  On a
mesh the bytes are the whole model's and the time is the first chip's, so
the peak is the mesh's (chips times one chip's)."""

from bench.accounting import (decode_device_ns, rows_by_step,
                              step_least_bytes, traced_steps)


def read(run):
    steps = traced_steps(run)
    if not steps or not run.peaks:
        return None
    ns = decode_device_ns(run)
    if ns <= 0:
        return None
    rows = rows_by_step(run)
    nbytes = sum(step_least_bytes(run, rows[i]) for i in steps if rows[i])
    peak = run.chips * run.peaks["hbm_bytes_per_s"]
    return 100.0 * nbytes / (ns * 1e-9 * peak)
