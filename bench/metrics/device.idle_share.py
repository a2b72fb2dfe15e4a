"""Device: 1 - the union of device-op intervals over the traced slice of the
window, averaged over the chips used, in percent."""


def read(run):
    tr = run.trace
    if not tr or tr["window_ns"] <= 0:
        return None
    busy = sum(tr["busy_ns"].values()) / len(tr["busy_ns"])
    return 100.0 * (1.0 - busy / tr["window_ns"])
