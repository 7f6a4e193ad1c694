"""Serving driver: batched requests through the locality-queue router.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b \
        --requests 12 --replicas 3 --policy locality

Runs the arch at its published width; ``--smoke`` shrinks it to the reduced
same-family config (CPU).  Compares router policies on the same workload
(multi-turn sessions whose follow-ups have cache affinity to the replica
that served turn one) and prints the locality/steal statistics next to the
generated tokens.  Each request's own timeline (``Request.timing``) is
printed as one line: the replica that served it, its queue wait (submit to
grab), its prefill (grab to first token, with the host time of the cache
set-up and of the prefill dispatch within it) and the host microseconds per
later token spent dispatching the decode step, handing off to the fetch and
fetching the token.  The greedy sample runs on the device inside the
prefill and decode programs, and each decode step is dispatched before the
token ahead of it is fetched: ``sample`` is now only the host's hand-off
from a dispatch to the fetch (about a microsecond), and
``Request.timing.decode_steps`` counts the decode programs dispatched,
``max_new - 1`` a request.  For a mixture-of-experts model the line also
gives the routed (token, choice) pairs over its MoE layers and how many of
them landed on an expert this replica holds.
"""
from __future__ import annotations

import argparse
from typing import Any

import jax
import numpy as np

from ..configs import get_config, reduce_config
from ..models.model import Model, build_model
from ..serving.engine import Request, ServeStats, ServingEngine
from .cache import use_compile_cache

MAX_SEQ = 64


def synth_requests(n: int, vocab: int, num_replicas: int,
                   seed: int = 0) -> list[Request]:
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        plen = int(rng.integers(8, 24))
        toks = rng.integers(0, vocab, size=plen)
        # ~2/3 of requests are session follow-ups with a cached prefix home
        home = int(rng.integers(0, num_replicas)) if rng.random() < 0.67 else -1
        reqs.append(Request(uid=i, tokens=toks, max_new=8, home_replica=home))
    return reqs


def build(arch: str, smoke: bool, seed: int = 0) -> tuple[Model, Any]:
    """The model for ``arch`` (reduced if ``smoke``) and seeded params."""
    cfg = get_config(arch)
    if smoke:
        cfg = reduce_config(cfg)
    model = build_model(cfg, max_pos=256)
    return model, jax.jit(model.init_params)(jax.random.key(seed))


def serve(model: Model, params: Any, policy: str, requests: int,
          replicas: int, seed: int = 0) -> tuple[list[Request], ServeStats]:
    """Serve ``synth_requests`` under ``policy``; requests sorted by uid."""
    engine = ServingEngine(model, params, num_replicas=replicas,
                           max_seq=MAX_SEQ, policy=policy)
    for req in synth_requests(requests, model.cfg.vocab_size, replicas,
                              seed=seed):
        engine.submit(req)
    done = engine.run_until_drained()
    return sorted(done, key=lambda r: r.uid), engine.stats


def timeline_line(req: Request) -> str:
    """One request's timeline as the operator reads it."""
    t = req.timing
    per_tok = 1e6 / max(len(req.out_tokens) - 1, 1)
    return (f"req {req.uid:3d} replica={t.replica} "
            f"queue_ms={(t.t_grab - t.t_submit) * 1e3:.3f} "
            f"prefill_ms={(t.t_first - t.t_grab) * 1e3:.3f} "
            f"(cache {t.cache_init_s * 1e3:.3f}, "
            f"dispatch {t.prefill_s * 1e3:.3f}) "
            f"per_token_us dispatch={t.dispatch_s * per_tok:.1f} "
            f"sample={t.sample_s * per_tok:.1f} "
            f"fetch={t.fetch_s * per_tok:.1f} "
            f"expert_choices={t.expert_choices} "
            f"held_expert_choices={t.held_expert_choices}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config (CPU)")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--replicas", type=int, default=3)
    ap.add_argument("--policy", default="locality",
                    choices=["locality", "round_robin", "single_queue"])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    use_compile_cache()
    model, params = build(args.arch, args.smoke, args.seed)
    done, s = serve(model, params, args.policy, args.requests, args.replicas,
                    args.seed)
    for req in done[:5]:
        print(f"req {req.uid:3d} -> {req.out_tokens}")
    for req in done:
        print(timeline_line(req))
    print(f"policy={args.policy} served={s.served} "
          f"local={s.locality_fraction:.2f} stolen={s.stolen} "
          f"prefill_tokens={s.prefill_tokens}")


if __name__ == "__main__":
    main()
