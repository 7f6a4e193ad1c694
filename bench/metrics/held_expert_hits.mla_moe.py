"""Held experts chosen per routed token per MoE layer: the requests'
``held_expert_choices`` over their ``expert_choices`` (``Request.timing``,
counted on the device over prefill and decode), times the experts a token
chooses.  Under a router that favours no expert it reads top_k * held /
experts: 6 * 8 / 64 = 0.75."""
from bench import work_mla_moe


def read(ctx, win, trace):
    return work_mla_moe.held_hits(win)
