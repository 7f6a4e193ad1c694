"""Serving engine: continuous batching on the locality-aware runtime.

This is the substrate where the paper's scheduler survives as a genuinely
*on-line* component on TPU: requests arrive dynamically, and replicas (model
instances on device slices) race to serve them — exactly the OpenMP
consumer-thread picture.  The router is a ``repro.runtime.Executor`` with
replicas as locality domains:

  * each request carries a locality tag = the replica holding its KV/prefix
    cache (requests in a multi-turn session are "first-touched" by the
    replica that prefilled them) — the runtime ``Task.home``;
  * one FIFO queue per replica; a free replica serves its own queue first
    and steals from the longest foreign queue otherwise (balance over
    locality, §2.2) — ``DomainQueues(steal_order="longest")``;
  * a stolen request pays a "page migration": its prefix must be re-prefilled
    on the stealing replica (the nonlocal-access penalty) — the runtime's
    ``steal_penalty`` account.

Routing policies:
  ``locality``     — route to the home replica's queue (homeless requests
                     round-robin); the paper's layer.
  ``round_robin``  — ignore homes on submit; queues + stealing still apply.
  ``single_queue`` — one shared FIFO (a single locality domain): replicas
                     take work in arrival order, locality is accidental.

The engine runs the real model (prefill + decode steps) for every request,
each step one jitted program with its greedy sample (``greedy_prefill``,
``greedy_decode_step``) that every replica of the model shares;
tests/test_serving.py checks the outputs are identical under every routing
policy while the steal/local statistics differ as the paper predicts.

Pass ``trace=repro.trace.TraceRecorder()`` to record the router's behaviour
as a replayable trace (steal-storm analysis / offline policy A/B without
re-running the model).

Continuous batching (``batch=``): a free replica drains up to ``batch``
queued requests from one queue per scheduling round and serves them as one
grab (``Executor(batch=...)`` + ``Replica.run_batch``) — pass an int or an
adaptive ``repro.control.BatchGovernor``.  Each request in the grab still
runs its own prefill + decode on its own cache, so batched serving is
token-identical to unbatched under every routing policy (the bit-identity
contract; a fused padded-batch decode is a later kernel-level step).  Pass
``control=repro.control.ControlLoop(...)`` to attach the full control
plane (cost routing, adaptive batching, the steal circuit-breaker) to the
engine's router.

Each request records its own timeline (``Request.timing``, a ``Timing``):
host ``time.perf_counter`` stamps at submit, at the start of the grab that
serves it, at its first and last token fetched, the replica that served
it, and the host seconds of each phase of ``Replica.run``.  The stamps are
on the clock of the benchmark's spans, and a profiled run maps them onto
the device trace's clock by one span's two ends.

Spec construction (the preferred path): pass
``spec=repro.spec.RuntimeSpec`` with a ``serving`` block —
``spec.named("controlled_serving")`` is the canonical example — and the
engine builds its whole router from the spec: queues, steal order,
governor (+ breaker), penalty rule, batch policy, and control plane all
come from the declared configuration, and traces recorded off the engine
embed the spec (schema v2), so ``repro.trace.replay(trace)`` reconstructs
the exact router with no hand-written factory.  The raw kwargs
(``policy``/``num_replicas``/``max_seq``/``pool_cap``/``batch``/
``control``) remain as a thin deprecated path.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..models.model import Model
from ..runtime import Executor, Task, Worker
from ..trace import TraceRecorder

POLICIES = ("locality", "round_robin", "single_queue")


@dataclasses.dataclass
class Timing:
    """One request's timeline, in host ``time.perf_counter`` seconds.

    The stamps are ``None`` until known; ``Replica.run`` called on its own
    (no engine) leaves ``t_submit``, ``t_grab`` and ``replica`` unset.  The
    greedy sample runs on the device, inside the prefill and decode
    programs, and the loop dispatches decode step n+1 before it fetches
    token n.  The three decode sums cover the tokens after the first, so
    that ``dispatch_s + sample_s + fetch_s == t_last - t_first`` up to the
    cost of the stamps themselves; the first decode step is dispatched
    before the first token's fetch, so it lies in ``t_first - t_grab``.
    """
    t_submit: Optional[float] = None    # ServingEngine.submit
    t_grab: Optional[float] = None      # the grab that serves it starts
    t_first: Optional[float] = None     # first token fetched to the host
    t_last: Optional[float] = None      # last token fetched to the host
    replica: int = -1                   # the worker that served it
    cache_init_s: float = 0.0           # init_cache
    prefill_s: float = 0.0              # dispatching the prefill program
    dispatch_s: float = 0.0             # dispatching decode steps
    sample_s: float = 0.0               # hand-off from a dispatch to a fetch
    fetch_s: float = 0.0                # the blocking fetch of each token
    decode_steps: int = 0               # decode programs dispatched
    # routed (token, choice) pairs over every MoE layer, prefill and
    # decode, and those on an expert this replica holds; counted on the
    # device in the cache and fetched once, after ``t_last``
    expert_choices: int = 0
    held_expert_choices: int = 0


@dataclasses.dataclass
class Request:
    uid: int
    tokens: np.ndarray              # prompt tokens (1D)
    max_new: int
    home_replica: int = -1          # -1: no cached prefix anywhere
    out_tokens: list[int] = dataclasses.field(default_factory=list)
    timing: Timing = dataclasses.field(default_factory=Timing)


@dataclasses.dataclass
class ServeStats:
    served: int = 0
    local: int = 0
    stolen: int = 0
    prefill_tokens: int = 0         # includes re-prefills caused by steals

    @property
    def locality_fraction(self) -> float:
        return self.local / max(self.served, 1)


EXPERT_COUNTERS = ("expert_choices", "held_expert_choices")


def expert_counts(caches: Any) -> dict[str, int]:
    """The MoE layers' counters of ``caches`` (batch row 0), summed over
    the layers: one transfer, none for a model without experts."""
    found: dict[str, list] = {k: [] for k in EXPERT_COUNTERS}
    for path, leaf in jax.tree_util.tree_leaves_with_path(caches):
        name = getattr(path[-1], "key", None)
        if name in found:
            found[name].append(leaf[..., 0])
    if not found["expert_choices"]:
        return {k: 0 for k in EXPERT_COUNTERS}
    host = jax.device_get(found)
    return {k: int(sum(np.sum(a) for a in v)) for k, v in host.items()}


def _greedy(logits: jax.Array) -> jax.Array:
    """The greedy token of the last position: (batch, 1) int32."""
    return jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]


# The model is a static argument, so every replica of one model shares one
# compiled program per shape.
@functools.partial(jax.jit, static_argnums=0)
def greedy_prefill(model: Model, params: Any, tokens: jax.Array,
                   caches: Any) -> tuple[jax.Array, Any]:
    """``model.prefill`` and the greedy sample, as one program."""
    logits, caches = model.prefill(params, {"tokens": tokens}, caches)
    return _greedy(logits), caches


@functools.partial(jax.jit, static_argnums=0)
def greedy_decode_step(model: Model, params: Any, token: jax.Array, pos,
                       caches: Any) -> tuple[jax.Array, Any]:
    """``model.decode_step`` on the last token and the greedy sample, as one
    program; ``pos`` is traced, so no position compiles a program."""
    logits, caches = model.decode_step(params, token, pos, caches)
    return _greedy(logits), caches


class Replica:
    """One model replica with its own KV-cache arena."""

    def __init__(self, model: Model, params: Any, max_seq: int,
                 batch_size: int = 1):
        self.model = model
        self.params = params
        self.max_seq = max_seq
        self.batch = batch_size

    def run(self, req: Request) -> Request:
        """Prefill and greedy decode ``req.max_new`` tokens, appending each
        to ``req.out_tokens`` as soon as it reaches the host.  Decode step
        n+1 is dispatched before token n is fetched, so the device has the
        next step queued while the host waits; ``max_new - 1`` decode steps
        run in all."""
        clock, tm = time.perf_counter, req.timing
        t0 = clock()
        caches = self.model.init_cache(1, self.max_seq)
        t1 = clock()
        prompt = np.asarray(req.tokens, np.int32)[None]
        tok, caches = greedy_prefill(self.model, self.params, prompt, caches)
        tok.copy_to_host_async()
        t2 = clock()
        tm.cache_init_s, tm.prefill_s = t1 - t0, t2 - t1
        pos = prompt.shape[1]
        dispatch = sample = fetch = 0.0
        last = t2
        for i in range(req.max_new):
            nxt = None
            if i + 1 < req.max_new:
                nxt, caches = greedy_decode_step(self.model, self.params,
                                                 tok, pos + i, caches)
                nxt.copy_to_host_async()
                tm.decode_steps += 1
            dispatched = clock()
            handed = clock()
            req.out_tokens.append(int(np.asarray(tok)[0, 0]))
            fetched = clock()
            if i == 0:
                tm.t_first = fetched
            else:
                dispatch += dispatched - last
                sample += handed - dispatched
                fetch += fetched - handed
            tm.t_last = last = fetched
            tok = nxt
        tm.dispatch_s, tm.sample_s, tm.fetch_s = dispatch, sample, fetch
        counts = expert_counts(caches)
        tm.expert_choices = counts["expert_choices"]
        tm.held_expert_choices = counts["held_expert_choices"]
        return req

    def run_batch(self, reqs: list[Request]) -> list[Request]:
        """Serve one coalesced grab of requests on this replica.

        Requests are decoded per-request on their own caches (the compiled
        prefill/decode functions are shared), so the batch is token-identical
        to serving each request alone — the batching win lives in the
        scheduler (one routing round, one queue grab, one cache arena touch
        per batch), not in fused device math yet.
        """
        return [self.run(r) for r in reqs]


class ServingEngine:
    """Replicas as locality domains over a ``runtime.Executor``."""

    def __init__(self, model: Model, params: Any, num_replicas: int = 2,
                 max_seq: int = 128, policy: str = "locality",
                 pool_cap: Optional[int] = 256,
                 trace: Optional[TraceRecorder] = None,
                 batch: Any = 1,
                 control: Optional[Any] = None,
                 spec: Optional[Any] = None):
        if spec is not None:
            conflicts = [name for name, val, default in (
                ("num_replicas", num_replicas, 2), ("max_seq", max_seq, 128),
                ("policy", policy, "locality"), ("pool_cap", pool_cap, 256),
                ("batch", batch, 1), ("control", control, None))
                if val != default]
            if conflicts:
                from ..spec import SpecError
                raise SpecError(
                    f"spec-built engine: {conflicts} come from the spec "
                    f"(serving/runtime blocks); drop the kwargs")
            self._init_from_spec(model, params, spec, trace)
            return
        if policy not in POLICIES:
            raise ValueError(policy)
        self.policy = policy
        self.replicas = [Replica(model, params, max_seq)
                         for _ in range(num_replicas)]
        # single_queue = one shared locality domain every replica serves;
        # otherwise one domain per replica (worker wid == replica index).
        num_domains = 1 if policy == "single_queue" else num_replicas
        worker_domains = ([0] * num_replicas if policy == "single_queue"
                          else list(range(num_replicas)))
        # every grab (batched or size 1) goes through the batch handler, so
        # there is exactly one accounting/migration path
        self._exec = Executor(
            num_domains, worker_domains,
            batch=batch,
            batch_handler=self._run_grab,
            steal_order="longest",
            steal_penalty=self._steal_penalty,
            pool_cap=pool_cap,
        )
        # optional control plane (repro.control.ControlLoop): cost routing,
        # adaptive batch sizing, storm circuit-breaking on this router.
        # Attached before the trace recorder so a recorded header names the
        # effective (possibly breaker-wrapped) governor.
        self.control = control
        if control is not None:
            control.attach(self._exec)
        # optional trace hook: record this engine's routing/steal behaviour
        # as a replayable repro.trace trace (request payloads stay opaque;
        # the submission stream carries home replica + prompt-length cost).
        self.trace = trace
        if trace is not None:
            trace.attach(self._exec)
        self._prefill_base = 0      # first-prefill tokens of served requests
        self._accidental_local = 0  # served by home replica, any routing

    def _init_from_spec(self, model: Model, params: Any, spec: Any,
                        trace: Optional[TraceRecorder]) -> None:
        """Build the whole router from a ``repro.spec.RuntimeSpec``."""
        from ..spec import SpecError
        if spec.serving is None:
            raise SpecError("ServingEngine needs a spec with a serving "
                            "block (see spec.named('controlled_serving'))")
        sv = spec.serving
        expected = 1 if sv.policy == "single_queue" else sv.num_replicas
        if spec.num_domains != expected:
            raise SpecError(
                f"serving policy {sv.policy!r} with {sv.num_replicas} "
                f"replicas needs num_domains == {expected}, "
                f"spec says {spec.num_domains}")
        wd = spec.worker_domains
        if wd is not None and len(wd) != sv.num_replicas:
            raise SpecError(f"worker_domains pins {len(wd)} workers but "
                            f"serving declares {sv.num_replicas} replicas")
        if sv.policy != "locality" and spec.router.kind != "none":
            # round_robin/single_queue submit with an explicit domain, so a
            # declared router would never be consulted — and the recorded
            # header would then name a policy that never ran.
            raise SpecError(
                f"serving policy {sv.policy!r} routes explicitly and would "
                f"silently bypass router.kind={spec.router.kind!r}; use "
                "policy 'locality' with a router, or router.kind 'none'")
        if sv.policy == "single_queue" and wd is None:
            # default one-worker-per-domain would under-staff the single
            # shared queue; every replica serves domain 0.
            spec = dataclasses.replace(spec,
                                       worker_domains=(0,) * sv.num_replicas)
        if trace is not None and spec.trace.record:
            raise SpecError("spec already declares trace recording; drop "
                            "the trace= kwarg (use Built.recorder instead)")
        self.policy = sv.policy
        self.replicas = [Replica(model, params, sv.max_seq)
                         for _ in range(sv.num_replicas)]
        built = spec.build(batch_handler=self._run_grab)
        self._exec = built.executor
        self.control = built.control
        self.trace = built.recorder
        if trace is not None:
            trace.attach(self._exec)
            self.trace = trace
        self._prefill_base = 0
        self._accidental_local = 0

    # -- runtime callbacks ---------------------------------------------------
    def _steal_penalty(self, task: Task, worker: Worker) -> float:
        # nonlocal access: a cached prefix must be re-prefilled on the thief
        req: Request = task.payload
        return float(len(req.tokens)) if req.home_replica >= 0 else 0.0

    def _touch(self, req: Request, worker: Worker) -> Request:
        self._prefill_base += len(req.tokens)
        if req.home_replica == worker.wid:
            self._accidental_local += 1
        req.home_replica = worker.wid          # first touch / migration
        return req

    def _run_grab(self, tasks: list[Task], worker: Worker) -> list[Request]:
        reqs = [self._touch(task.payload, worker) for task in tasks]
        t = time.perf_counter()
        for req in reqs:
            req.timing.t_grab, req.timing.replica = t, worker.wid
        return self.replicas[worker.wid].run_batch(reqs)

    # -- public API ----------------------------------------------------------
    def submit(self, req: Request) -> None:
        req.timing.t_submit = time.perf_counter()
        task = self._exec.make_task(payload=req, home=req.home_replica,
                                    cost=float(len(req.tokens)))
        if self.policy == "single_queue":
            domain = 0
        elif self.policy == "round_robin":
            domain = self._exec.next_round_robin()
        else:
            domain = None        # Executor routes: home queue, else round-robin
        self._exec.submit(task, domain=domain)

    def run_until_drained(self) -> list[Request]:
        """Round-robin replica stepping (a discrete stand-in for parallel
        replica workers — ordering, not timing, is what's under test)."""
        return self._exec.run_until_drained()

    @property
    def runtime(self) -> Executor:
        return self._exec

    @property
    def stats(self) -> ServeStats:
        s = self._exec.stats
        # single_queue collapses all replicas onto one domain, so the
        # runtime's domain-based local counter can't see which replica
        # served a request; accidental home hits are counted in the handler
        # instead (there are no steals with a single domain to exclude).
        local = (self._accidental_local if self.policy == "single_queue"
                 else s.local)
        return ServeStats(
            served=s.executed,
            local=local,
            stolen=s.stolen,
            prefill_tokens=self._prefill_base + int(s.steal_penalty),
        )
