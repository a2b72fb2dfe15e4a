"""Work the window's steps needed, from the run record and the shapes.

Shared by the per-layer metric readers: which steps lie in the window or in
the traced slice, the operations the tokens actually computed, the least
bytes a decode step must move, and the least time of each kernel's calls.
A step's rows are the tokens it emitted (its live requests): padding rows,
dead slots and cache capacity beyond a row's valid positions are work the
program may do, but not work the algorithm needs.
"""

from __future__ import annotations

import collections

from bench import flops as F
from bench import trace as T

#: What each kernel's device ops mention in the trace (the jitted
#: wrapper's name in the op's name stack).
KERNEL_PATTERNS = {
    "flash_decode": ("flash_decode",),
    "entropy_exit": ("entropy_exit",),
}


def window_steps(run) -> list[int]:
    t0, t1 = run.window
    return [i for i, s in enumerate(run.steps)
            if t0 <= s.start and s.end <= t1]


def itl_ms(run) -> list[float]:
    """Every gap between consecutive tokens of a request, in milliseconds,
    where the later token falls in the window."""
    t0, t1 = run.window
    return [1e3 * (b - a) for r in run.reqs.values()
            for a, b in zip(r.emits, r.emits[1:]) if t0 < b <= t1]


def traced_steps(run) -> list[int]:
    if not run.trace:
        return []
    i0, i1 = run.trace["steps"]
    return list(range(i0, i1))


def rows_by_step(run) -> dict[int, list]:
    out = collections.defaultdict(list)
    for tok in run.tokens:
        out[tok[0]].append(tok)
    return out


def token_flops(run, tok) -> int:
    """One decoded token: the edge layers and the exit heads for every live
    row, the later layers and the main head for a row that ran on."""
    d, cut = run.dims, run.depth_cut
    _, pos, exited, deep_ctx, _ = tok
    out = cut * F.token_layer_flops(d, pos + 1) \
        + len(run.branches) * F.head_flops(d)
    if not exited:
        out += (d.n_layers - cut) * F.token_layer_flops(d, deep_ctx) \
            + F.head_flops(d)
    return out


def window_flops(run) -> int:
    """Decoded tokens and admission prefills of the window's steps."""
    steps = set(window_steps(run))
    total = sum(token_flops(run, t) for t in run.tokens if t[0] in steps)
    for i in steps:
        for rid in run.steps[i].admitted:
            total += F.prefill_flops(run.dims, run.reqs[rid].plen)
    return total


def step_least_bytes(run, rows: list) -> int:
    """Least bytes one decode step moves: each executed segment's weights
    once (the exit heads' and the main head's unembedding with them) and
    the K/V of the positions each live row attends to."""
    d, cut = run.dims, run.depth_cut
    deep = d.n_layers - cut
    total = cut * d.layer_weight_bytes()
    if run.branches:
        total += F.head_weight_bytes(d)
    total += sum(F.kv_bytes(d, cut, pos + 1) for _, pos, _, _, _ in rows)
    survivors = [r for r in rows if not r[2]]
    if survivors:
        total += deep * d.layer_weight_bytes() + F.head_weight_bytes(d)
        total += sum(F.kv_bytes(d, deep, ctx) for _, _, _, ctx, _ in
                     survivors)
    return total


def kernel_least_seconds(run, kernel: str, steps) -> tuple[float, str]:
    """Least time of every call of ``kernel`` in ``steps``, from the live
    rows' shapes, and the bound that dominates it."""
    d, cut = run.dims, run.depth_cut
    rows = rows_by_step(run)
    total, bound = 0.0, collections.Counter()
    for i in steps:
        live = rows.get(i, [])
        calls = []
        if kernel == "flash_decode":
            edge = [pos + 1 for _, pos, _, _, _ in live]
            deep = [ctx for _, _, exited, ctx, _ in live if not exited]
            calls.append((cut, F.flash_decode_cost(
                edge, d.n_heads, d.n_kv_heads, d.head_dim)))
            if deep:
                calls.append((d.n_layers - cut, F.flash_decode_cost(
                    deep, d.n_heads, d.n_kv_heads, d.head_dim)))
        elif kernel == "entropy_exit" and run.branches and live:
            calls.append((1, F.entropy_exit_cost(
                len(run.branches), len(live), d.vocab)))
        for n, (fl, nb) in calls:
            t, which = F.least_seconds(fl, nb, run.peaks)
            total += n * t
            bound[which] += n * t
    return total, (bound.most_common(1)[0][0] if bound else "")


def kernel_device_ns(run, kernel: str) -> float:
    """Summed device time of the kernel's ops in the traced slice, on the
    first chip."""
    tr = run.trace
    chip = tr["chips"][0]
    return T.matching_ns(tr["ops"][chip], KERNEL_PATTERNS[kernel],
                         tr["lo"], tr["hi"])


def decode_device_ns(run) -> float:
    """Device time of the programs other than admission prefills in the
    traced slice, on the first chip."""
    tr = run.trace
    chip = tr["chips"][0]
    mods = [e for e in tr["modules"][chip] if not e.mentions("prefill")]
    return sum(b - a for a, b in T.merged(T.clip(mods, tr["lo"], tr["hi"])))


def kernel_roofline(run, kernel: str):
    """Least time of the kernel's traced calls over their device time, in
    percent; None where the trace holds no call of it."""
    steps = traced_steps(run)
    if not steps or not run.peaks:
        return None
    ns = kernel_device_ns(run, kernel)
    least, _ = kernel_least_seconds(run, kernel, steps)
    if ns <= 0 or least <= 0:
        return None
    return 100.0 * least / (ns * 1e-9)
