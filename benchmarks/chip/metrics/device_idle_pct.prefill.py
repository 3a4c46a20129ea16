"""device_idle_pct.prefill: share of the traced window in which no operation
ran on the device, averaged over the cell's chips (atomic mixes)."""


def read(run):
    t = run.trace
    if t is None or run.kind != "atomic" or not t.busy_ns:
        return None
    busy = sum(t.busy_ns.values()) / len(t.busy_ns)
    return 100.0 * (1.0 - busy / t.window_ns)
