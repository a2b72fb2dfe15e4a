"""Server / tier runtime: the 95th percentile of the window's gaps between
consecutive tokens, in milliseconds: the tail beyond the end-to-end 90th
percentile, the longest stalls of a request's stream."""

import numpy as np

from bench.accounting import itl_ms


def read(run):
    gaps = itl_ms(run)
    if not gaps:
        return None
    return float(np.percentile(gaps, 95))
