"""Chat sessions, open loop, through ``repro.serving.engine.ServingEngine``.

The engine is built through its spec path from the traffic's ``spec``
(the locality router over the replicas as locality domains).  Requests are
submitted at their due times and served by ``engine.runtime.step()``
between submissions.  Each request's ``out_tokens`` is a list that stamps
every token as the engine appends it, after the device result reached the
host, so the program is not changed to be timed.

End-to-end metrics, over every request due in the window (arrivals stop
when it closes; the requests due in it are followed to their end, for up
to ``drain_limit_s`` more):

- ``ttft_p90_ms``: due time to first token, 90th percentile;
- ``tpot_p90_ms``: (last token - first token) / (tokens - 1), 90th
  percentile.

The cell runs below the knee, so every request finishes on a fixed
schedule and the tokens completed in the window read the offered load:
the tails are its end-to-end metrics.

The check: a sample drawn from the seed of the finished requests, with the
longest among them, is run through the float32 reference, prompt and
served tokens together; the reading is the widest gap by which a served
token's reference logit lies below the reference's best at its position.
"""
from __future__ import annotations

import dataclasses
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import harness as H
from bench import sessions as S
from bench.reference import qwen2 as ref

# published config key -> the program's ModelConfig field
PROGRAM_FIELDS = {"num_hidden_layers": "num_layers", "hidden_size": "d_model",
                  "num_attention_heads": "num_heads",
                  "num_key_value_heads": "num_kv_heads",
                  "intermediate_size": "d_ff", "vocab_size": "vocab_size",
                  "rope_theta": "rope_theta"}


class TimedTokens(list):
    """A token list that stamps each append on the host clock."""

    def __init__(self):
        super().__init__()
        self.times: list[float] = []

    def append(self, tok) -> None:
        self.times.append(time.perf_counter())
        super().append(tok)


def program_model(conf: dict):
    """The program's model at the configuration's sizes."""
    from repro.configs import get_config
    from repro.models.model import build_model
    kw = {field: conf[key] for key, field in PROGRAM_FIELDS.items()}
    kw["head_dim"] = conf["hidden_size"] // conf["num_attention_heads"]
    return build_model(dataclasses.replace(get_config(conf["program_arch"]), **kw))


def _same_layout(params, model) -> None:
    want = jax.eval_shape(model.init_params, jax.random.key(0))
    got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    if got != jax.tree.map(lambda a: (a.shape, a.dtype), want):
        raise RuntimeError("the program's parameter tree no longer matches "
                           "the benchmark's weights")


def setup(ctx):
    return ServeCell(ctx)


class ServeCell:
    def __init__(self, ctx: H.Context):
        from repro import spec
        from repro.serving.engine import Request, ServingEngine
        self.ctx = ctx
        conf, tr = ctx.cell.config, ctx.cell.traffic
        self.model = program_model(conf)
        params = ref.make_params(conf, H.prng_key(ctx.seed))
        _same_layout(params, self.model)
        self.engine = ServingEngine(self.model, params,
                                    spec=spec.RuntimeSpec.from_dict(tr["spec"]))
        self.grab_start: dict[int, float] = {}
        for rep in self.engine.replicas:
            rep.run_batch = self._timed_grab(rep.run_batch)
        # warm-up: every prompt length the traffic can send, on every
        # replica, with the decode step and the host-side token ops
        zeros = np.zeros(tr["cap"], np.int32)
        for rep in self.engine.replicas:
            for n in range(tr["round_to"], tr["cap"] + 1, tr["round_to"]):
                rep.run(Request(uid=-1, tokens=zeros[:n], max_new=2))
        self.requests: list = []

    def _timed_grab(self, run_batch):
        spans = self.ctx.spans

        def grab(reqs):
            t = time.perf_counter()
            for r in reqs:
                self.grab_start[r.uid] = t
            with spans("bench.replica_grab"):
                return run_batch(reqs)
        return grab

    def window(self, seconds: float) -> H.Window:
        from repro.serving.engine import Request
        tr, spans = self.ctx.cell.traffic, self.ctx.spans
        sessions = S.schedule(tr, seconds)
        rng = np.random.default_rng([self.ctx.seed, 2])
        vocab = self.ctx.cell.config["vocab_size"]
        new = [[rng.integers(0, vocab, t.new_tokens, dtype=np.int32) for t in s]
               for s in sessions]
        reqs: list[list] = [[] for _ in sessions]
        submitted: dict[int, float] = {}
        ex = self.engine.runtime
        t0 = time.perf_counter()
        while True:
            now = time.perf_counter() - t0
            wake = None
            for si, s in enumerate(sessions):
                j = len(reqs[si])
                if j == len(s) or (j and not _done(reqs[si][-1])):
                    continue
                if s[j].due > now:
                    wake = s[j].due if wake is None else min(wake, s[j].due)
                    continue
                prev = reqs[si][-1] if j else None
                toks = new[si][j] if prev is None else np.concatenate(
                    [prev.tokens, np.asarray(prev.out_tokens, np.int32),
                     new[si][j]])
                req = Request(uid=len(submitted), tokens=toks,
                              max_new=s[j].max_new,
                              home_replica=-1 if prev is None else prev.home_replica,
                              out_tokens=TimedTokens())
                with spans("bench.submit"):
                    self.engine.submit(req)
                submitted[req.uid] = now - s[j].due
                reqs[si].append(req)
            if now > seconds + tr["drain_limit_s"]:
                break
            if len(ex):
                with spans("bench.step"):
                    ex.step()
            elif wake is None:
                break
            else:
                with spans("bench.idle"):
                    time.sleep(max(0.0, wake - (time.perf_counter() - t0)))
        turns = [(t, r) for s, rs in zip(sessions, reqs) for t, r in zip(s, rs)]
        due = sum(len(s) for s in sessions)
        finished = [(t, r) for t, r in turns if _done(r)]
        self.requests = [r for _, r in finished]
        ms = 1e3
        ttft = [(r.out_tokens.times[0] - t0 - t.due) * ms for t, r in finished]
        tpot = [(r.out_tokens.times[-1] - r.out_tokens.times[0])
                / (len(r.out_tokens) - 1) * ms for _, r in finished]
        late = sorted(submitted.values())
        if late:
            H.log(f"serve: {due} requests due, {len(finished)} finished; "
                  f"generator late by median {H.percentile(late, 50):.4f} s, "
                  f"max {late[-1]:.4f} s")
        if not finished:
            raise RuntimeError("no request finished in the window")
        return H.Window(
            seconds=seconds, attempted=due, failed=due - len(finished),
            metrics={"ttft_p90_ms": H.percentile(ttft, 90),
                     "tpot_p90_ms": H.percentile(tpot, 90)},
            facts={"t0": t0, "turns": finished, "grab_start": self.grab_start,
                   "sizes": self.ctx.cell.config})

    def release(self) -> None:
        self.engine = self.model = None
        gc.collect()

    def _sample(self) -> list:
        """The requests the check reads: the longest finished one and
        ``check_sample - 1`` others drawn from the seed."""
        reqs = self.requests
        rng = np.random.default_rng([self.ctx.seed, 3])
        longest = max(range(len(reqs)),
                      key=lambda i: len(reqs[i].tokens) + reqs[i].max_new)
        rest = [i for i in range(len(reqs)) if i != longest]
        k = min(len(rest), self.ctx.cell.traffic["check_sample"] - 1)
        picked = [longest] + sorted(rng.choice(rest, k, replace=False).tolist())
        return [reqs[i] for i in picked]

    def _max_gap(self, gaps_of) -> tuple[float, int]:
        conf, tr = self.ctx.cell.config, self.ctx.cell.traffic
        params = ref.make_params(conf, H.prng_key(self.ctx.seed), jnp.float32)
        max_seq, out_max = tr["spec"]["serving"]["max_seq"], tr["output"]["max"]
        worst, n_tokens = 0.0, 0
        for r in self._sample():
            toks, at, target = served_positions(r.tokens, list(r.out_tokens),
                                                max_seq, out_max)
            g = np.asarray(gaps_of(params, conf, toks, at, target))
            gap = float(np.max(g[:len(r.out_tokens)]))
            # a token the reference cannot place (out of the vocabulary)
            # reads not-a-number: no limit admits it
            worst = max(worst, gap if np.isfinite(gap) else 1e300)
            n_tokens += len(r.out_tokens)
        return worst, n_tokens

    def control(self) -> dict[str, float]:
        """The control's reading: at the same positions of the same
        requests, the gap of the token the float8 reference puts first."""
        worst, _ = self._max_gap(
            lambda p, c, toks, at, target: ref.control_gaps(p, c, toks, at))
        return {"max_logit_gap": worst}

    def check(self) -> dict[str, tuple[float, float]]:
        worst, n_tokens = self._max_gap(ref.gaps)
        H.log(f"serve: checked {n_tokens} served tokens against the float32 "
              f"reference")
        limit = float(self.ctx.cell.config["limits"]["max_logit_gap"])
        return {"max_logit_gap": (worst, limit)}


def served_positions(prompt, out: list[int], max_seq: int, out_max: int):
    """The sequence the reference reads (prompt and served tokens, padded
    to ``max_seq``), the positions after which each token was served and
    the tokens, both padded to ``out_max``."""
    seq = np.zeros(max_seq, np.int32)
    full = np.concatenate([np.asarray(prompt, np.int32),
                           np.asarray(out[:-1], np.int32)])
    seq[:len(full)] = full
    at = np.zeros(out_max, np.int32)
    target = np.zeros(out_max, np.int32)
    at[:len(out)] = len(prompt) - 1 + np.arange(len(out))
    target[:len(out)] = out
    return jnp.asarray(seq), jnp.asarray(at), jnp.asarray(target)


def _done(req) -> bool:
    return len(req.out_tokens) == req.max_new
