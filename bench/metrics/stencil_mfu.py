"""The whole sweep's share of the chips' peak: the least time of the sweeps
completed in the traced window (the larger of the FLOP and the HBM-byte
share; the bytes bind) over the window's seconds."""
from bench import work


def read(ctx, win, trace):
    flops, nbytes = work.jacobi_sweep(win.facts["sites"])
    least = work.least_time(flops, nbytes, ctx.peaks, ctx.cell.chips)
    return 100.0 * win.facts["sweeps"] * least / win.seconds
