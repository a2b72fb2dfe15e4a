"""Model step: operations the window's tokens needed (prefill tokens, and
per decoded row the layers and heads it ran through with attention over its
real context, no padding rows) over the window's seconds times chips times
the chip's bf16 peak, in percent."""

from bench.accounting import window_flops


def read(run):
    t0, t1 = run.window
    if not run.peaks or t1 <= t0:
        return None
    peak = (t1 - t0) * run.chips * run.peaks["bf16_flops"]
    return 100.0 * window_flops(run) / peak
