"""Chip benchmark of the BranchyNet serving path.

``python3 -m bench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json``.  Everything that belongs to one model
configuration, one traffic mix, one cell's correctness limits or one
per-layer metric is a file of its own, found by the name that
``BENCHMARK.json`` gives it:

    bench/configs/<config>.json      published sizes, tier plan, slots,
                                     context, mesh, what was assumed
    bench/references/<family>.py     the plain float32 reference of a family
    bench/traffic/<traffic>.json     parameters of the one traffic generator
    bench/limits/<workload>.json     the limits that decide ``correct``, with
                                     the readings they were set from
    bench/metrics/<metric>.py        one reader per per-layer metric

The rest of this package is the yardstick shared by every cell: the peaks
table (``peaks``), operation and byte counts (``flops``, ``accounting``),
the trace reduction (``trace``), the seeded weights (``weights``), the
compile count (``clock``) and the served window with its comparison to the
reference (``harness``).  ``sweep`` finds an open-loop cell's knee and
``readings`` reads many seeds and the float8 control in one process; the
benchmark's own runs use neither.
"""
