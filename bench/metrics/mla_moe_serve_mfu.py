"""The decode step's share of the chip's peak, for the DeepSeek-V2 cell:
for the requests finished in the traced window, the least time of their
decode steps (``work_mla_moe.decode_step``: each weight read once, the held
experts chosen as the counter reads, the latent cache up to each position;
the larger of FLOP and byte share) over the wall time from first to last
token."""
from bench import work, work_mla_moe


def read(ctx, win, trace):
    hits = work_mla_moe.held_hits(win)
    if hits is None:
        return None
    sizes = win.facts["sizes"]
    least = sum(work.least_time(*work_mla_moe.decode_step(sizes, c, hits),
                                ctx.peaks)
                for c in work_mla_moe.decode_contexts(win))
    wall = sum(r.out_tokens.times[-1] - r.out_tokens.times[0]
               for _, r in win.facts["turns"])
    return 100.0 * least / wall if wall > 0 else None
