"""Device time in the halo exchange over busy time, in %, mean over the
chips: the ``collective-permute`` operations (``ppermute``) that lie
wholly in the window.  The all-gather of the window's row sample is left
out, so the metric reads the sweep's own exchange."""

HALO = r"collective-permute"


def read(ctx, win, trace):
    if trace is None or not trace.devices:
        return None
    shares = []
    for dev in trace.devices:
        busy = sum(b - a for a, b in trace.busy_intervals(dev))
        halo = sum(e.end - e.start for e in trace.matching_ops(dev, HALO))
        if busy > 0:
            shares.append(halo / busy)
    if not any(shares):
        return None
    return 100.0 * sum(shares) / len(shares)
