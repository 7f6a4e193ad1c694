"""From the start of the grab that served a request to its first token
fetched (``Request.timing``): cache allocation, prefill, the first sample
and the first fetch; 90th percentile over the requests finished in the
traced window, in ms."""
from bench import timeline


def read(ctx, win, trace):
    return timeline.p90_ms(win, "t_grab", "t_first")
