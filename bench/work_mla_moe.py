"""The work a DeepSeek-V2 decode step needs, from the configuration's
shapes alone, whatever implements it.

A batch-1 decode step reads once: every weight of attention (the latent
form: queries absorbed into the latent, so the keys and values are never
expanded), of the leading dense layers, of each MoE layer's router and
shared experts, the routed experts of this chip that the token chose, the
head's vocabulary slice and the token's embedding row; and the latent cache
(``kv_lora_rank`` + ``qk_rope_head_dim`` a position a layer) up to the
position.  Its operations are two a weight read and, per layer and cached
position, the latent scores and the latent value sum.  The router is
stored as float32.
"""
from __future__ import annotations

from bench.reference.deepseek_v2 import dims


def decode_step(sizes: dict, context: int, held_hits: float,
                itemsize: int = 2) -> tuple[float, float]:
    """(flops, bytes) of one batch-1 decode step over ``context`` cached
    positions, where ``held_hits`` experts held here are chosen in each
    MoE layer."""
    s = dims(sizes)
    d, h, r, dr = s["d"], s["h"], s["r"], s["dr"]
    n_moe = s["layers"] - s["dense"]
    attn = (d * h * (s["dn"] + dr) + d * (r + dr)
            + r * h * (s["dn"] + s["dv"]) + h * s["dv"] * d)
    expert = 3 * d * s["fe"]
    norms = s["layers"] * (2 * d + r) + d
    router = d * s["e"]
    matmul = (s["layers"] * attn + s["dense"] * 3 * d * s["f"]
              + n_moe * (router + s["shared"] * expert + held_hits * expert)
              + d * s["v"])
    cache = s["layers"] * context * (r + dr)
    flops = 2.0 * matmul + s["layers"] * 2.0 * h * context * (2 * r + dr)
    nbytes = ((matmul + d + norms + cache) * itemsize
              + n_moe * router * (4 - itemsize))
    return flops, float(nbytes)


def held_hits(win) -> float | None:
    """Held experts chosen a token per MoE layer, from the requests' own
    counters (``Request.timing``): the held share of the routed choices
    times the experts a token chooses.  ``None`` where the program keeps no
    such counter."""
    held = choices = 0
    for _, r in win.facts["turns"]:
        t = getattr(r, "timing", None)
        if t is None or not hasattr(t, "held_expert_choices"):
            return None
        held += t.held_expert_choices
        choices += t.expert_choices
    if choices <= 0:
        return None
    return held / choices * win.facts["sizes"]["num_experts_per_tok"]


def decode_contexts(win) -> list[int]:
    """The context of every decode step of the requests finished in the
    window: a request's token i (i >= 1) attends over prompt + i."""
    return [len(r.tokens) + i for _, r in win.facts["turns"]
            for i in range(1, len(r.out_tokens))]
