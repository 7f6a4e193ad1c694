"""The gap between consecutive tokens of a request, 90th percentile over
every such gap of the requests finished in the traced window, in ms: the
tail of ``tpot_p90_ms`` taken over thousands of gaps, not tens of requests."""
from bench import harness as H


def read(ctx, win, trace):
    gaps = [(b - a) * 1e3 for _, r in win.facts["turns"]
            for a, b in zip(r.out_tokens.times, r.out_tokens.times[1:])]
    return H.percentile(gaps, 90) if gaps else None
