"""The decode step's share of the chip's peak: for the requests finished
in the traced window, the least time of their decode steps (weights and
the cache up to each position read once; the larger of FLOP and byte
share) over the wall time from first to last token."""
from bench import work


def read(ctx, win, trace):
    sizes = win.facts["sizes"]
    least = wall = 0.0
    for _, r in win.facts["turns"]:
        times = r.out_tokens.times
        plen = len(r.tokens)
        for i in range(1, len(times)):
            flops, nbytes = work.qwen2_decode_step(sizes, plen + i)
            least += work.least_time(flops, nbytes, ctx.peaks)
        wall += times[-1] - times[0]
    return 100.0 * least / wall if wall > 0 else None
