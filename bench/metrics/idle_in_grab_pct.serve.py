"""The share of the traced window, in %, in which some request lies
between the start of the grab that serves it and its last token
(``Request.timing``, mapped onto the trace's clock by ``bench.window``)
while the device runs no operation.  The rest of ``idle_share.serve`` is
the wait for arrivals."""
from bench import timeline


def read(ctx, win, trace):
    share = timeline.idle_in_grab_share(ctx, win, trace)
    return None if share is None else 100.0 * share
