"""What the serving cell's program-span metrics read: each request's own
timeline, ``Request.timing``, which the serving engine stamps on
``time.perf_counter`` (the clock of the benchmark's spans) as it serves
the request.

A program whose requests carry no timeline gives these metrics nothing to
read: every function here then returns ``None``.
"""
from __future__ import annotations

from typing import Callable

from bench import harness as H
from bench.xplane import merge


def timings(win) -> list | None:
    """``(request, timing)`` of every request finished in the window, or
    ``None`` where the program keeps no timeline."""
    out = [(r, getattr(r, "timing", None)) for _, r in win.facts["turns"]]
    if not out or any(t is None for _, t in out):
        return None
    return out


def p90_ms(win, start: str, end: str) -> float | None:
    """90th percentile over the requests of ``end - start``, in ms."""
    ts = timings(win)
    if ts is None:
        return None
    return H.percentile([(getattr(t, end) - getattr(t, start)) * 1e3
                         for _, t in ts], 90)


def per_decode_token_us(win, field: str) -> float | None:
    """The sum of one decode counter over the requests, over the tokens
    they were served after their first, in microseconds."""
    ts = timings(win)
    if ts is None:
        return None
    tokens = sum(len(r.out_tokens) - 1 for r, _ in ts)
    if tokens <= 0:
        return None
    return 1e6 * sum(getattr(t, field) for _, t in ts) / tokens


def clock_map(host: tuple[float, float],
              traced: tuple[float, float]) -> Callable[[float], float]:
    """The linear map from the host clock onto the trace's that takes one
    span's ends as the host recorded them (``host``) onto the same span's
    ends in the trace (``traced``): it absorbs the clocks' offset and any
    difference of rate."""
    (h0, h1), (d0, d1) = host, traced
    rate = (d1 - d0) / (h1 - h0)
    return lambda t: d0 + (t - h0) * rate


def overlap_s(a: list[tuple[float, float]], b: list[tuple[float, float]]) -> float:
    """Seconds common to two sorted lists of disjoint intervals."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(0.0, hi - lo)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_in_grab_share(ctx, win, trace) -> float | None:
    """The share of the traced window in which some request lies between
    its grab and its last token while the first device runs no operation."""
    ts = timings(win)
    if ts is None or trace is None or not trace.devices:
        return None
    lo, hi = trace.window
    to_trace = clock_map(ctx.spans.records["bench.window"][0], (lo, hi))
    grabs = merge([(max(lo, to_trace(t.t_grab)), min(hi, to_trace(t.t_last)))
                   for _, t in ts])
    grabs = [(a, b) for a, b in grabs if b > a]
    in_grab = sum(b - a for a, b in grabs)
    busy = overlap_s(grabs, trace.busy_intervals(trace.devices[0]))
    return (in_grab - busy) / trace.window_s
