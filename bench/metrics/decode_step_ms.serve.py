"""Median device time of one decode program call, in ms, from the trace's
program events."""
import statistics

PROGRAM = r"decode_step"


def read(ctx, win, trace):
    if trace is None or not trace.devices:
        return None
    calls = trace.matching_modules(trace.devices[0], PROGRAM)
    if not calls:
        return None
    return 1e3 * statistics.median(e.end - e.start for e in calls)
