"""The router's own wait: from ``ServingEngine.submit`` to the start of the
grab that served the request (``Request.timing``), 90th percentile over the
requests finished in the traced window, in ms.  Unlike
``queue_wait_p90_ms.serve`` it leaves out how late the generator ran."""
from bench import timeline


def read(ctx, win, trace):
    return timeline.p90_ms(win, "t_submit", "t_grab")
