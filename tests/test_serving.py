"""Serving engine: router policies preserve outputs, change locality stats."""
import jax
import numpy as np
import pytest

from repro.configs import get_config, reduce_config
from repro.models.model import build_model
from repro.serving.engine import (Replica, Request, ServingEngine, Timing,
                                  greedy_decode_step, greedy_prefill)


@pytest.fixture(scope="module")
def small_model():
    cfg = reduce_config(get_config("qwen2-0.5b"))
    model = build_model(cfg, max_pos=96)
    params = model.init_params(jax.random.key(0))
    return cfg, model, params


POLICIES = ("locality", "round_robin", "single_queue")


@pytest.fixture(scope="module")
def greedy_expect(small_model):
    """Three prompts, each with the seven tokens ``_plain_greedy`` decodes
    after it (greedy, so any shorter answer is a prefix of these)."""
    cfg, model, params = small_model
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab_size, size=n) for n in (5, 7, 11)]
    return [(toks, _plain_greedy(model, params, toks, 7)) for toks in prompts]


def _requests(cfg, n=8, replicas=2, seed=0):
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        toks = rng.integers(0, cfg.vocab_size, size=int(rng.integers(6, 14)))
        home = int(rng.integers(0, replicas)) if rng.random() < 0.7 else -1
        out.append(Request(uid=i, tokens=toks, max_new=4, home_replica=home))
    return out


class TestRouterPolicies:
    def test_outputs_identical_across_policies(self, small_model):
        cfg, model, params = small_model
        outs = {}
        for policy in ("locality", "round_robin", "single_queue"):
            eng = ServingEngine(model, params, num_replicas=2, max_seq=64,
                                policy=policy)
            for r in _requests(cfg):
                eng.submit(r)
            done = eng.run_until_drained()
            outs[policy] = {r.uid: tuple(r.out_tokens) for r in done}
        assert outs["locality"] == outs["round_robin"] == outs["single_queue"]

    def test_locality_policy_maximizes_local_fraction(self, small_model):
        cfg, model, params = small_model
        stats = {}
        for policy in ("locality", "round_robin"):
            eng = ServingEngine(model, params, num_replicas=2, max_seq=64,
                                policy=policy)
            for r in _requests(cfg, n=12, seed=2):
                eng.submit(r)
            eng.run_until_drained()
            stats[policy] = eng.stats
        assert stats["locality"].locality_fraction >= \
            stats["round_robin"].locality_fraction

    def test_steal_happens_under_skewed_load(self, small_model):
        cfg, model, params = small_model
        eng = ServingEngine(model, params, num_replicas=2, max_seq=64,
                            policy="locality")
        # all requests homed on replica 0: replica 1 must steal
        rng = np.random.default_rng(1)
        for i in range(6):
            toks = rng.integers(0, cfg.vocab_size, size=8)
            eng.submit(Request(uid=i, tokens=toks, max_new=2, home_replica=0))
        eng.run_until_drained()
        assert eng.stats.stolen > 0
        assert eng.stats.served == 6

    def test_trace_hook_records_replayable_router_trace(self, small_model):
        from repro import trace as rtrace
        cfg, model, params = small_model
        rec = rtrace.TraceRecorder()
        eng = ServingEngine(model, params, num_replicas=2, max_seq=64,
                            policy="locality", trace=rec)
        for r in _requests(cfg, n=8, seed=3):
            eng.submit(r)
        eng.run_until_drained()
        t = rec.finish()
        assert t.n_tasks == 8
        assert t.stats["executed"] == eng.stats.served
        # submission costs carry the prompt length (the engine's task cost)
        assert all(s.cost >= 1 for s in t.submissions)
        # the recorded router schedule replays deterministically (payloads
        # are opaque, so replay re-decides scheduling, not decoding)
        res = rtrace.replay(t, lambda tr: rtrace.executor_from_meta(
            tr, steal_penalty=lambda task, w: task.cost))
        assert res.stats["executed"] == 8

    @pytest.mark.parametrize("max_new", (1, 2, 7))
    @pytest.mark.parametrize("runner", ("replica",) + POLICIES)
    def test_greedy_decode_matches_model(self, small_model, greedy_expect,
                                         runner, max_new):
        """Tokens == hand-rolled prefill+argmax decode, through
        ``Replica.run`` alone and through the engine under every policy,
        with one decode program dispatched per token after the first."""
        cfg, model, params = small_model
        reqs = [Request(uid=i, tokens=toks, max_new=max_new)
                for i, (toks, _) in enumerate(greedy_expect)]
        if runner == "replica":
            rep = Replica(model, params, 64)
            done = [rep.run(r) for r in reqs]
        else:
            eng = ServingEngine(model, params, num_replicas=2, max_seq=64,
                                policy=runner)
            for r in reqs:
                eng.submit(r)
            done = eng.run_until_drained()
        assert len(done) == len(reqs)
        for r in done:
            assert r.out_tokens == greedy_expect[r.uid][1][:max_new]
            assert r.timing.decode_steps == max_new - 1


def _plain_greedy(model, params, toks, max_new, max_seq=64):
    """Prefill and greedy decode with no engine: the tokens to expect."""
    import jax.numpy as jnp
    caches = model.init_cache(1, max_seq)
    logits, caches = model.prefill(
        params, {"tokens": jnp.asarray(toks, jnp.int32)[None]}, caches)
    pos, out = len(toks), []
    cur = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
    for _ in range(max_new):
        out.append(int(cur[0, 0]))
        logits, caches = model.decode_step(params, cur, pos, caches)
        cur = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
        pos += 1
    return out


@pytest.fixture(scope="module")
def timelines(small_model):
    """Per policy: the served requests and, by uid, the index of the
    replica whose ``run_batch`` served each."""
    cfg, model, params = small_model
    out = {}
    for policy in POLICIES:
        eng = ServingEngine(model, params, num_replicas=2, max_seq=64,
                            policy=policy)
        served_by = {}
        for i, rep in enumerate(eng.replicas):
            def grab(reqs, _run=rep.run_batch, _i=i):
                served_by.update((r.uid, _i) for r in reqs)
                return _run(reqs)
            rep.run_batch = grab
        for r in _requests(cfg, n=6, seed=4):
            r.max_new = 6
            eng.submit(r)
        out[policy] = (eng.run_until_drained(), served_by)
    return out


@pytest.mark.parametrize("policy", POLICIES)
class TestRequestTimeline:
    def test_timeline_is_ordered(self, timelines, policy):
        done, _ = timelines[policy]
        assert len(done) == 6
        for r in done:
            t = r.timing
            assert t.t_submit <= t.t_grab <= t.t_first <= t.t_last
            assert t.cache_init_s > 0 and t.prefill_s > 0

    def test_replica_names_the_worker_that_served(self, timelines, policy):
        done, served_by = timelines[policy]
        assert {r.uid: r.timing.replica for r in done} == served_by

    def test_decode_sums_make_up_first_to_last_token(self, timelines, policy):
        done, _ = timelines[policy]
        for r in done:
            t = r.timing
            span = t.t_last - t.t_first
            parts = t.dispatch_s + t.sample_s + t.fetch_s
            assert span > 0 and min(t.dispatch_s, t.sample_s, t.fetch_s) > 0
            assert abs(parts - span) <= 0.05 * span

    def test_tokens_are_unchanged(self, small_model, timelines, policy):
        cfg, model, params = small_model
        done, _ = timelines[policy]
        want = {r.uid: _plain_greedy(model, params, r.tokens, 6)
                for r in _requests(cfg, n=6, seed=4)}
        assert {r.uid: r.out_tokens for r in done} == want


def test_replica_run_alone_leaves_the_engine_stamps_unset(small_model):
    cfg, model, params = small_model
    req = Replica(model, params, 64).run(
        Request(uid=0, tokens=np.arange(5), max_new=3))
    t = req.timing
    assert t.t_submit is None and t.t_grab is None and t.replica == -1
    assert t.t_first <= t.t_last and len(req.out_tokens) == 3


def test_operator_line_reads_each_request_timeline(small_model):
    from repro.launch.serve import serve, timeline_line
    cfg, model, params = small_model
    done, _ = serve(model, params, "locality", requests=3, replicas=2)
    for req in done:
        line = timeline_line(req)
        assert line.startswith(f"req {req.uid:3d} replica={req.timing.replica} ")
        fields = dict(kv.split("=") for kv in line.replace(",", "").split()
                      if "=" in kv)
        assert set(fields) == {"replica", "queue_ms", "prefill_ms", "dispatch",
                               "sample", "fetch", "expert_choices",
                               "held_expert_choices"}
        assert all(float(v) >= 0 for v in fields.values())


class _StepsAtAppend(list):
    """A token list that records, at each append, how many decode steps
    the request had dispatched."""

    def __init__(self, timing):
        super().__init__()
        self.timing, self.steps = timing, []

    def append(self, tok) -> None:
        self.steps.append(self.timing.decode_steps)
        super().append(tok)


@pytest.mark.parametrize("max_new", (1, 2, 7))
def test_each_token_is_appended_one_step_ahead(small_model, greedy_expect,
                                               max_new):
    """Token n reaches the caller's list alone, once step n+1 (if any) is
    dispatched and before step n+2 is."""
    cfg, model, params = small_model
    toks, want = greedy_expect[0]
    timing = Timing()
    out = _StepsAtAppend(timing)
    req = Request(uid=0, tokens=toks, max_new=max_new, out_tokens=out,
                  timing=timing)
    assert Replica(model, params, 64).run(req).out_tokens is out
    assert out == want[:max_new]
    assert out.steps == [min(n + 1, max_new - 1) for n in range(max_new)]


def test_replicas_share_one_compiled_program_per_shape(small_model):
    """Four replicas of one model compile one decode program and one
    prefill program per prompt length between them."""
    cfg, _, params = small_model
    model = build_model(cfg, max_pos=96)     # a model nothing compiled yet
    eng = ServingEngine(model, params, num_replicas=4, max_seq=64,
                        policy="round_robin")
    prefills = greedy_prefill._cache_size()
    decodes = greedy_decode_step._cache_size()
    rng = np.random.default_rng(5)
    for i in range(8):
        toks = rng.integers(0, cfg.vocab_size, size=(6, 9)[i % 2])
        eng.submit(Request(uid=i, tokens=toks, max_new=3))
    done = eng.run_until_drained()
    assert {r.timing.replica for r in done} == {0, 1, 2, 3}
    assert greedy_prefill._cache_size() - prefills == 2
    assert greedy_decode_step._cache_size() - decodes == 1
