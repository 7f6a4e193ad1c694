"""The device's idle share of the traced window, mean over the chips."""


def read(ctx, win, trace):
    share = None if trace is None else trace.idle_share()
    return None if share is None else 100.0 * share
