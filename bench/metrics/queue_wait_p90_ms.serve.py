"""Due time to the start of the replica grab that served the request, 90th
percentile over the requests finished in the traced window, in ms."""
from bench import harness as H


def read(ctx, win, trace):
    t0, starts = win.facts["t0"], win.facts["grab_start"]
    waits = [(starts[r.uid] - t0 - t.due) * 1e3 for t, r in win.facts["turns"]
             if r.uid in starts]
    return H.percentile(waits, 90) if waits else None
