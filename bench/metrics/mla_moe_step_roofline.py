"""The decode step's share of its roofline, for the DeepSeek-V2 cell: the
least time of one decode step at the window's mean context
(``work_mla_moe.decode_step``, the held experts chosen as the counter
reads) over the median device time of the decode program's calls in the
trace (``greedy_decode_step``: model step and greedy sample)."""
import statistics

from bench import work, work_mla_moe

PROGRAM = r"decode_step"


def read(ctx, win, trace):
    if trace is None or not trace.devices:
        return None
    calls = trace.matching_modules(trace.devices[0], PROGRAM)
    hits = work_mla_moe.held_hits(win)
    contexts = work_mla_moe.decode_contexts(win)
    if not calls or hits is None or not contexts:
        return None
    step = statistics.median(e.end - e.start for e in calls)
    context = round(sum(contexts) / len(contexts))
    least = work.least_time(*work_mla_moe.decode_step(
        win.facts["sizes"], context, hits), ctx.peaks)
    return 100.0 * least / step
