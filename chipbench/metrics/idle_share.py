"""Share of the traced window in which no op ran on the device, averaged
over the devices."""


def read(run):
    tr = run.trace
    if tr is None or not tr.busy:
        return None
    lo, hi = tr.window()
    busy = sum(tr.busy_ns(d, lo, hi) for d in range(len(tr.busy)))
    return 100.0 * (1.0 - busy / len(tr.busy) / (hi - lo))
