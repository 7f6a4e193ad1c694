"""The Pallas Jacobi kernel's share of its roofline: the least time for one
sweep's algorithmic bytes (one read and one write of the lattice, at the
peak bandwidth) over the mean device time of the kernel's calls that lie
wholly inside the traced window.  One call is one sweep."""
from bench import work

KERNEL = r"^%jacobi_sweep_pallas\S* custom-call$"


def read(ctx, win, trace):
    if trace is None or not trace.devices:
        return None
    calls = trace.matching_ops(trace.devices[0], KERNEL)
    if not calls:
        return None
    per_call = sum(e.end - e.start for e in calls) / len(calls)
    flops, nbytes = work.jacobi_sweep(win.facts["sites"])
    return 100.0 * work.least_time(flops, nbytes, ctx.peaks) / per_call
