"""Kernels: the least time of the traced steps' fused entropy / exit /
argmax calls, from the live rows' shapes (the larger of operations over
peak and bytes over HBM bandwidth) over the summed device time of the
kernel's trace events, in percent."""

from bench.accounting import kernel_roofline


def read(run):
    return kernel_roofline(run, "entropy_exit")
