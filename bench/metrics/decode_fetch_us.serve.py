"""Host time per decode token spent in the blocking fetch of the token to
the host, in us: the sum of ``Request.timing.fetch_s`` over the
requests finished in the traced window, over the tokens they were served
after their first."""
from bench import timeline


def read(ctx, win, trace):
    return timeline.per_decode_token_us(win, "fetch_s")
