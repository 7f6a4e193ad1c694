"""Multi-turn chat sessions on an open-loop schedule, from a traffic file.

One general generator for the serving cells; a traffic mix is a file of
its parameters.  Sessions start as a Poisson process at
``session_rate_per_s``; each has a uniform number of turns in ``turns``.
A first turn's prompt is lognormal (``first_prompt``); a follow-up's prompt
is the previous prompt, the previous answer and a new lognormal segment
(``followup_segment``), and its home is the replica that served the
previous turn.  Every prompt is rounded up to a multiple of ``round_to``
with seeded tokens; a session ends at ``cap``.  Answers are lognormal
(``output``), clipped.  A follow-up is due an exponential think time
(``think_mean_s``) after the previous turn was due.

The sizes and due times come from the traffic's own ``schedule_seed``, so
every run does the same work; the run's seed draws the tokens (and the
weights), so no two seeds send the same prompts.  The draws per session
are fixed in number, so a shorter window sends a prefix of the same
schedule.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np


@dataclasses.dataclass
class Turn:
    session: int
    index: int
    due: float              # seconds after the window opens
    prompt_len: int
    new_tokens: int         # tokens appended after the previous turn's answer
    max_new: int


def _lognormal(rng, p: dict) -> float:
    return float(rng.lognormal(math.log(p["median"]), p["sigma"]))


def _round_up(n: float, m: int) -> int:
    return int(math.ceil(n / m) * m)


def schedule(tr: dict, seconds: float) -> list[list[Turn]]:
    """The sessions that start in ``[0, seconds)``, each cut to the turns
    due in it; sessions in order of start."""
    rng = np.random.default_rng(tr["schedule_seed"])
    lo, hi = tr["turns"]
    m, cap = tr["round_to"], tr["cap"]
    out_p = tr["output"]
    sessions, t = [], 0.0
    while True:
        t += float(rng.exponential(1.0 / tr["session_rate_per_s"]))
        n_turns = int(rng.integers(lo, hi + 1))
        first = _lognormal(rng, tr["first_prompt"])
        segs = [_lognormal(rng, tr["followup_segment"]) for _ in range(hi)]
        outs = [int(np.clip(round(_lognormal(rng, out_p)), out_p["min"],
                            out_p["max"])) for _ in range(hi)]
        thinks = [float(rng.exponential(tr["think_mean_s"])) for _ in range(hi)]
        if t >= seconds:
            return sessions
        turns, due, prev = [], t, 0
        for j in range(n_turns):
            if j:
                due += thinks[j]
                want = prev + outs[j - 1] + segs[j]
            else:
                want = first
            length = min(max(_round_up(want, m), m), cap)
            if due >= seconds or (j and _round_up(want, m) > cap):
                break
            turns.append(Turn(len(sessions), j, due, length,
                              length - (prev + outs[j - 1] if j else 0),
                              outs[j]))
            prev = length
        sessions.append(turns)
