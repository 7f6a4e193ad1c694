"""Tests for the online runtime: queue primitives, the event log, and the
executor."""
import dataclasses
import warnings

import numpy as np
import pytest

from repro.runtime import (AdaptiveSteal, DomainQueues, Event, EventLog,
                           Executor, NoSteal, SubmissionPool)
from runtime_reference import (ReferenceEventLog, ReferenceQueues,
                               reference_executor)


def _cyclic(n):
    return DomainQueues(n, steal_order="cyclic")


def _pair(got):
    """A dequeue as ``(item, stolen)``, or None."""
    return None if got is None else (got.item, got.stolen)


class TestLocalityQueuesEdgeCases:
    def test_steal_scan_wraparound_order(self):
        # caller in LD 2 of 4; work only in LDs 0 and 3.  The cyclic scan
        # starts right after the local domain: 3 -> 0 -> 1, so LD 3 is hit
        # first even though LD 0 was filled first.
        q = _cyclic(4)
        q.enqueue(10, 0)
        q.enqueue(30, 3)
        assert _pair(q.dequeue(2)) == (30, True)
        assert _pair(q.dequeue(2)) == (10, True)

    def test_steal_scan_wraps_past_zero(self):
        # caller in LD 1 of 3; scan order is 2 -> 0 (wraps past the end)
        q = _cyclic(3)
        q.enqueue(7, 0)
        assert _pair(q.dequeue(1)) == (7, True)

    def test_dequeue_all_empty(self):
        q = _cyclic(3)
        for ld in range(3):
            assert q.dequeue(ld) is None
        assert len(q) == 0
        # drained queues behave the same as never-filled ones
        q.enqueue(1, 0)
        assert _pair(q.dequeue(0)) == (1, False)
        assert q.dequeue(0) is None
        assert len(q) == 0

    def test_local_pop_preferred_and_fifo(self):
        q = _cyclic(2)
        for blk in (1, 2, 3):
            q.enqueue(blk, 1)
        q.enqueue(9, 0)
        assert _pair(q.dequeue(1)) == (1, False)    # FIFO within the LD
        assert _pair(q.dequeue(1)) == (2, False)
        assert _pair(q.dequeue(0)) == (9, False)    # local wins while nonempty
        assert _pair(q.dequeue(0)) == (3, True)

    def test_sizes_consistent_under_interleaving(self):
        rng = np.random.default_rng(42)
        q = _cyclic(4)
        live = 0
        for step in range(500):
            if rng.random() < 0.55:
                q.enqueue(step, int(rng.integers(4)))
                live += 1
            else:
                got = q.dequeue(int(rng.integers(4)))
                if got is not None:
                    live -= 1
                else:
                    assert live == 0
            sizes = q.queue_sizes()
            assert sum(sizes) == len(q) == live
            assert all(s >= 0 for s in sizes)


class TestDomainQueues:
    def test_longest_steal_order_with_tie_break(self):
        q = DomainQueues(4, steal_order="longest")
        q.enqueue("a", 1)
        q.enqueue("b", 3)
        q.enqueue("c", 3)
        got = q.dequeue(0)
        assert (got.item, got.domain, got.stolen) == ("b", 3, True)
        # now 1 and 3 are tied at depth 1: lowest domain id wins
        got = q.dequeue(0)
        assert (got.item, got.domain) == ("a", 1)

    def test_min_victim_threshold(self):
        q = DomainQueues(2)
        q.enqueue("x", 1)
        assert q.dequeue(0, min_victim=2) is None       # too shallow to rob
        assert len(q) == 1
        q.enqueue("y", 1)
        got = q.dequeue(0, min_victim=2)
        assert got.item == "x" and got.stolen

    def test_allow_steal_false(self):
        q = DomainQueues(2)
        q.enqueue("x", 1)
        assert q.dequeue(0, allow_steal=False) is None
        assert q.dequeue(1).stolen is False

    def test_random_steal_needs_rng(self):
        with pytest.raises(ValueError):
            DomainQueues(2, steal_order="random")

    @pytest.mark.parametrize("domain", [-1, 3])
    def test_enqueue_refuses_domain_out_of_range(self, domain):
        q = DomainQueues(3)
        with pytest.raises(ValueError, match=f"domain {domain} outside the "
                                             "queues' 3 domains"):
            q.enqueue("x", domain)
        assert len(q) == 0 and q.queue_sizes() == [0, 0, 0]


class TestSubmissionPool:
    def test_fifo_and_cap_accounting(self):
        p = SubmissionPool(cap=3)
        for i in range(3):
            p.push(i)
        assert p.full and p.free_slots == 0
        assert p.pop() == 0
        assert not p.full and p.free_slots == 1
        assert [p.pop(), p.pop(), p.pop()] == [1, 2, None]


class TestEventLog:
    def test_ring_overflow_counts_vs_window(self):
        # counts() covers the whole run even after the ring buffer drops
        # the oldest events; len() is only the retained window.
        log = EventLog(maxlen=8)
        for i in range(20):
            log.emit(step=i, kind="run", worker=0, domain=0, task_uid=i)
        assert log.counts() == {"run": 20}
        assert log.total == 20
        assert len(log) == 8
        assert log.dropped == 12
        # the window keeps the *newest* events
        assert [e.task_uid for e in log] == list(range(12, 20))

    def test_csv_export_carries_window_marker(self):
        log = EventLog(maxlen=4)
        for i in range(6):
            log.emit(step=i, kind="run", worker=0, domain=0, task_uid=i,
                     cost=2.0)
        lines = log.to_csv_lines()
        assert lines[0].startswith("#")
        assert "total=6" in lines[0] and "retained=4" in lines[0] \
            and "dropped=2" in lines[0]
        assert lines[1].split(",")[:2] == ["step", "kind"]
        assert len(lines) == 2 + 4               # marker + header + window
        assert lines[2].endswith(",2,0")         # cost,penalty columns

    def test_steal_event_src_domain_is_victim_queue(self):
        # worker 1 (domain 1) can only steal from domain 0's queue; the
        # steal event must point at the victim, not the thief's domain.
        ex = Executor(num_domains=2)
        for i in range(4):
            ex.submit(ex.make_task(payload=i, home=0))
        ex.run_until_drained()
        steals = [e for e in ex.events if e.kind == "steal"]
        assert steals and all(e.src_domain == 0 for e in steals)
        assert all(e.domain == 1 and e.worker == 1 for e in steals)
        runs = [e for e in ex.events if e.kind == "run"]
        assert all(e.src_domain == e.domain for e in runs)

    def test_execution_events_carry_cost_and_penalty(self):
        ex = Executor(num_domains=2,
                      steal_penalty=lambda task, worker: 2.0 * task.cost)
        for i in range(4):
            ex.submit(ex.make_task(payload=i, home=0, cost=3.0))
        ex.run_until_drained()
        for e in ex.events:
            if e.kind == "steal":
                assert (e.cost, e.penalty, e.service) == (3.0, 6.0, 9.0)
            elif e.kind == "run":
                assert (e.cost, e.penalty) == (3.0, 0.0)


def _submit_n(ex, n, homes):
    for i in range(n):
        ex.submit(ex.make_task(payload=i, home=int(homes[i % len(homes)])))


class TestExecutor:
    def test_deterministic_per_seed(self):
        def run(seed):
            ex = Executor(num_domains=3, steal_order="random", seed=seed)
            _submit_n(ex, 30, [0, 0, 0, 1, 2])
            ex.run_until_drained()
            return ([(e.kind, e.worker, e.task_uid, e.src_domain)
                     for e in ex.events], ex.metrics.snapshot())
        assert run(7) == run(7)
        assert run(1) == run(1)

    def test_local_steal_stats_under_skew(self):
        # everything homed on domain 0 of 2: worker 1 can only steal
        ex = Executor(num_domains=2)
        _submit_n(ex, 10, [0])
        results = ex.run_until_drained()
        s = ex.stats
        assert len(results) == 10 and s.executed == 10
        assert s.stolen > 0 and s.local > 0
        assert s.local + s.stolen == 10          # nothing is both or neither
        assert ex.pool[1].stats.stolen == s.stolen
        assert abs(s.local_fraction + s.steal_fraction - 1.0) < 1e-9

    def test_all_local_when_balanced(self):
        ex = Executor(num_domains=2)
        _submit_n(ex, 10, [0, 1])
        ex.run_until_drained()
        assert ex.stats.local == 10 and ex.stats.stolen == 0

    def test_homeless_tasks_round_robin_and_never_local(self):
        ex = Executor(num_domains=2)
        _submit_n(ex, 8, [-1])
        ex.run_until_drained()
        s = ex.stats
        assert s.executed == 8
        assert s.local == 0                      # home -1 matches no domain
        assert s.stolen == 0                     # round-robin spread evenly

    def test_backpressure_bounds_pool_depth(self):
        ex = Executor(num_domains=2, pool_cap=8)
        _submit_n(ex, 100, [0, 1, 0, 0])         # skew so steals happen too
        ex.run_until_drained()
        s = ex.stats
        assert s.executed == 100
        assert s.max_pool_depth <= 8
        assert s.inline_runs > 0                 # the submitter had to help

    def test_steal_penalty_accounting(self):
        ex = Executor(num_domains=2,
                      steal_penalty=lambda task, worker: task.cost)
        for i in range(6):
            ex.submit(ex.make_task(payload=i, home=0, cost=3.0))
        ex.run_until_drained()
        s = ex.stats
        assert s.steal_penalty == pytest.approx(3.0 * s.stolen)

    def test_results_in_completion_order_and_cleared(self):
        ex = Executor(num_domains=2,
                      handler=lambda task, worker: (task.payload, worker.wid))
        _submit_n(ex, 6, [0, 1])
        out = ex.run_until_drained()
        assert sorted(p for p, _ in out) == list(range(6))
        assert ex.run_until_drained() == []      # drained and cleared

    def test_adaptive_steals_fewer_than_greedy(self):
        def drive(governor):
            ex = Executor(num_domains=2, governor=governor,
                          steal_penalty=lambda t, w: 6.0)
            uid = 0
            for _ in range(20):                  # online: 2 arrivals per round
                for _ in range(2):
                    ex.submit(ex.make_task(payload=uid, home=0))
                    uid += 1
                ex.step()
            ex.run_until_drained()
            return ex.stats
        greedy = drive(None)
        adaptive = drive(AdaptiveSteal(penalty_hint=6.0))
        assert greedy.executed == adaptive.executed == 40
        assert adaptive.stolen < greedy.stolen
        assert adaptive.steal_penalty < greedy.steal_penalty

    def test_no_steal_governor_still_drains(self):
        ex = Executor(num_domains=2, governor=NoSteal())
        _submit_n(ex, 12, [0, 1, 0])
        ex.run_until_drained()
        assert ex.stats.executed == 12 and ex.stats.stolen == 0
        assert ex.stats.local == 12

    def test_event_log_counts_match_stats(self):
        ex = Executor(num_domains=2)
        _submit_n(ex, 9, [0, 0, 1])
        ex.run_until_drained()
        counts = ex.events.counts()
        s = ex.stats
        assert counts["submit"] == s.submitted == 9
        assert counts.get("steal", 0) == s.stolen
        assert counts.get("run", 0) + counts.get("steal", 0) \
            + counts.get("inline", 0) == s.executed


class TestRuntimeJacobiPath:
    def test_runtime_sweep_matches_ref_any_policy(self):
        jnp = pytest.importorskip("jax.numpy")  # noqa: F841 (jax-backed ref)
        from repro.kernels.jacobi.ref import jacobi_sweep_ref
        from repro.stencil.jacobi import run_runtime_sweep

        rng = np.random.default_rng(3)
        f = rng.standard_normal((40, 8, 8)).astype(np.float32)
        ref = np.asarray(jacobi_sweep_ref(f))
        for gov, order in ((None, "cyclic"), (NoSteal(), "cyclic"),
                           (AdaptiveSteal(), "longest")):
            out, stats = run_runtime_sweep(f, di=5, num_domains=4,
                                           workers_per_domain=2, governor=gov,
                                           steal_order=order)
            assert np.array_equal(out, ref)
            assert stats.executed == 8


# -- queued-cost snapshot accounting -----------------------------------------

class _MutableTask:
    """Task stand-in whose ``cost`` can be rewritten while queued."""

    def __init__(self, uid: int, cost: float = 1.0):
        self.uid = uid
        self.cost = cost


class TestQueuedCostSnapshot:
    def test_mutating_queued_cost_cannot_drift_account(self):
        # regression: the pre-fix dequeue subtracted the task's *live* cost,
        # so repricing a queued task (MeasuredPenalty-style) drifted the
        # account — and a re-zero-on-empty mask hid the drift whenever the
        # queue happened to drain.  The snapshot accounting needs no mask:
        # the account returns to exactly 0.0 by construction.
        q = DomainQueues(2)
        t = _MutableTask(0, cost=2.5)
        q.enqueue(t, 0)
        t.cost = 1000.0                      # repriced while queued
        assert q.queue_costs() == [2.5, 0.0]  # account holds the snapshot
        got = q.dequeue(0)
        assert got.item is t and not got.stolen
        assert q.queue_costs() == [0.0, 0.0]  # exact zero, no drift residue

    def test_drift_free_even_when_queue_never_drains(self):
        # the old re-zero mask only fired on empty queues; with a second
        # task still queued the drift was permanent.  Snapshots make the
        # remaining account exactly the remaining snapshot.
        q = DomainQueues(1)
        a, b = _MutableTask(0, cost=3.0), _MutableTask(1, cost=4.0)
        q.enqueue(a, 0)
        q.enqueue(b, 0)
        a.cost = 99.0
        q.dequeue(0, False)
        assert q.queue_costs() == [4.0]
        assert q.cost(0) == 4.0

    def test_drain_budget_uses_snapshots(self):
        q = DomainQueues(1)
        tasks = [_MutableTask(uid, cost=c)
                 for uid, c in enumerate((1.0, 1.0, 5.0))]
        for t in tasks:
            q.enqueue(t, 0)
        tasks[2].cost = 0.0             # reprice the expensive tail task
        got = q.dequeue(0, False)
        assert got.item.uid == 0
        # budget consults the enqueue-time snapshot (5.0), not the live 0.0
        rest = q.drain(0, 2, budget=2.0, spent=1.0)
        assert [t.uid for t in rest] == [1]


class TestConstructionValidation:
    @pytest.mark.parametrize("bad", [0, -1, None])
    def test_event_log_rejects_degenerate_maxlen(self, bad):
        with pytest.raises(ValueError, match="maxlen"):
            EventLog(maxlen=bad)
        with pytest.raises(ValueError, match="maxlen"):
            ReferenceEventLog(maxlen=bad)

    @pytest.mark.parametrize("bad", [0, -3, None])
    def test_submission_pool_rejects_degenerate_cap(self, bad):
        with pytest.raises(ValueError, match="cap"):
            SubmissionPool(cap=bad)

    def test_minimal_valid_sizes_accepted(self):
        assert EventLog(maxlen=1).maxlen == 1
        assert SubmissionPool(cap=1).cap == 1


class TestOverflowWarningAttribution:
    @pytest.mark.parametrize("log_cls", [EventLog, ReferenceEventLog])
    def test_overflow_warning_points_at_emit_caller(self, log_cls):
        # stacklevel=2: the warning is attributed to emit's direct caller
        # (Executor._emit in executor-driven logs; this test here), not to
        # events.py internals and not to a frame above the caller.
        log = log_cls(maxlen=2)
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            for i in range(3):
                log.emit(i, "run", 0, 0, i)
        overflow = [w for w in caught
                    if issubclass(w.category, RuntimeWarning)]
        assert len(overflow) == 1          # one-shot
        assert overflow[0].filename == __file__
        assert "overflow" in str(overflow[0].message)


class TestColumnarEventLogEquivalence:
    def _emit_mixed(self, log, n=40):
        for i in range(n):
            kind = ("submit", "run", "steal", "idle", "probe")[i % 5]
            log.emit(step=i // 4, kind=kind, worker=i % 3, domain=i % 2,
                     task_uid=i, src_domain=i % 2 - 1, cost=0.5 * i,
                     penalty=float(i % 2))

    def test_matches_reference_log_through_overflow(self):
        log, ref = EventLog(maxlen=16), ReferenceEventLog(maxlen=16)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            self._emit_mixed(log)
            self._emit_mixed(ref)
        assert list(log) == list(ref)
        assert log.counts() == ref.counts()
        assert (log.total, log.dropped) == (ref.total, ref.dropped)
        assert log.tail(5) == ref.tail(5)
        assert log.to_csv_lines() == ref.to_csv_lines()

    def test_columns_export_matches_events_and_types(self):
        log = EventLog(maxlen=16)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            self._emit_mixed(log)
        cols = log.columns()
        names = log.kind_names()
        events = list(log)
        assert all(len(v) == len(events) for v in cols.values())
        assert cols["kind"].dtype == np.uint8
        assert cols["step"].dtype == np.int64
        assert cols["cost"].dtype == np.float64
        rebuilt = [Event(step=int(s), kind=names[k], worker=int(w),
                         domain=int(d), task_uid=int(u), src_domain=int(sd),
                         cost=float(c), penalty=float(p))
                   for s, k, w, d, u, sd, c, p in zip(
                       cols["step"], cols["kind"], cols["worker"],
                       cols["domain"], cols["task_uid"], cols["src_domain"],
                       cols["cost"], cols["penalty"])]
        assert rebuilt == events

    def test_empty_log_exports_empty_columns(self):
        cols = EventLog(maxlen=4).columns()
        assert all(len(v) == 0 for v in cols.values())


# -- randomized equivalence with the linear-scan reference (seeded + hypothesis)

def _run_equivalence_trial(seed: int, topo=None):
    """One randomized interleaving driven through ``DomainQueues`` and the
    linear-scan ``ReferenceQueues`` in lockstep: every Popped, the queue
    sizes, the cost accounts (held to the exact shadow snapshot sum), and
    the RNG state must stay identical — including under mid-queue cost
    mutation."""
    import random

    r = random.Random(seed)
    nd = topo.num_domains if topo is not None else r.choice([1, 2, 3, 4, 8])
    order = r.choice(DomainQueues.STEAL_ORDERS)
    rngs = [np.random.default_rng(seed) for _ in range(2)]
    pair = [cls(nd, steal_order=order, rng=g, topology=topo)
            for g, cls in zip(rngs, (DomainQueues, ReferenceQueues))]
    shadow = [0.0] * nd          # exact replay of the account arithmetic
    snaps = {}                   # uid -> enqueue-time cost snapshot
    live = []
    uid = 0
    for step in range(r.randint(40, 160)):
        op = r.random()
        if op < 0.45:
            d = r.randrange(nd)
            t = _MutableTask(uid, cost=r.choice([0.5, 1.0, 2.0, 3.5]))
            uid += 1
            live.append(t)
            snaps[t.uid] = t.cost
            shadow[d] += t.cost
            for q in pair:
                q.enqueue(t, d)
        elif op < 0.55 and live:
            r.choice(live).cost = r.choice([0.0, 7.7, 1e6])
        else:
            d = r.randrange(nd)
            mv = r.choice([1, 1, 2, 3, None])
            allow = r.random() > 0.1
            outs = [q.dequeue(d, allow) if mv is None
                    else q.dequeue(d, allow, mv) for q in pair]
            a, b = outs
            ta = None if a is None else (a.item.uid, a.domain, a.stolen,
                                         a.level, a.distance)
            tb = None if b is None else (b.item.uid, b.domain, b.stolen,
                                         b.level, b.distance)
            assert ta == tb, (seed, step, ta, tb)
            if a is not None:
                # subtract the enqueue-time snapshot, as the account does —
                # never the (possibly mutated) live cost
                shadow[a.domain] -= snaps[a.item.uid]
        assert pair[0].queue_sizes() == pair[1].queue_sizes(), (seed, step)
        assert pair[0].queue_costs() == pair[1].queue_costs(), (seed, step)
        assert pair[0].queue_costs() == shadow, (seed, step)
        s0, s1 = (g.bit_generator.state for g in rngs)
        assert s0 == s1, (seed, step, "rng draw sequences diverged")


class TestFastSlowEquivalenceRandomized:
    """Always-on seeded sweep of the bit-identity contract between the
    runtime and the linear-scan reference (the hypothesis property below
    explores further when hypothesis is installed; this fallback keeps the
    contract gated everywhere)."""

    def test_flat_topologies(self):
        for seed in range(60):
            _run_equivalence_trial(seed)

    def test_hierarchical_topologies(self):
        import random

        from repro.topology import grouped
        for seed in range(60):
            r = random.Random(10_000 + seed)
            topo = grouped(r.choice([[2, 2], [4, 4], [2, 2, 2, 2],
                                     [4, 2], [2, 3, 3]]))
            _run_equivalence_trial(10_000 + seed, topo=topo)

    def test_executor_level_equivalence_all_policies(self):
        # whole-executor check: identical stats, event streams, and results
        # against the reference queue and log for every steal order (events
        # compared through the columnar vs reference log CSV, so this also
        # pins the logs)
        for order in DomainQueues.STEAL_ORDERS:
            snaps = {}
            for make in (Executor, reference_executor):
                ex = make(4, steal_order=order, seed=7,
                          steal_penalty=lambda t, w: 4.0)
                rng = np.random.default_rng(42)
                for i in range(200):
                    home = int(rng.integers(-1, 4))
                    ex.submit(ex.make_task(home=home,
                                           cost=float(rng.choice(
                                               [0.5, 1.0, 2.0]))))
                    if i % 3 == 0:
                        ex.step()
                ex.run_until_drained()
                snaps[make] = (dataclasses.asdict(ex.stats),
                               ex.events.counts(),
                               tuple(ex.events.to_csv_lines()))
            assert snaps[Executor] == snaps[reference_executor], order

    def test_executor_equivalence_contended(self):
        # every task homed on domain 0, batch 4, cyclic, constant penalty:
        # worker 0 takes each wave of four whole, so every other worker's
        # grab is a victim scan over a machine with no foreign work
        snaps = {}
        for make in (Executor, reference_executor):
            ex = make(4, steal_order="cyclic", batch=4,
                      steal_penalty=lambda t, w: 4.0)
            with warnings.catch_warnings():
                warnings.simplefilter("ignore", RuntimeWarning)
                for i in range(20_000):
                    ex.submit(ex.make_task(home=0))
                    if i % 4 == 3:
                        ex.step()
                ex.run_until_drained()
            snaps[make] = (ex.metrics.snapshot(), ex.events.counts(),
                           tuple(ex.events.to_csv_lines()))
        assert snaps[Executor] == snaps[reference_executor]


class TestFastSlowEquivalenceHypothesis:
    """Property form of the contract, for machines with hypothesis."""

    def test_property_interleavings(self):
        hyp = pytest.importorskip("hypothesis")
        st = pytest.importorskip("hypothesis.strategies")

        @hyp.given(seed=st.integers(min_value=0, max_value=2**32 - 1),
                   hier=st.booleans())
        @hyp.settings(max_examples=200, deadline=None)
        def prop(seed, hier):
            if hier:
                import random

                from repro.topology import grouped
                r = random.Random(seed)
                topo = grouped(r.choice([[2, 2], [4, 4], [2, 2, 2, 2]]))
                _run_equivalence_trial(seed, topo=topo)
            else:
                _run_equivalence_trial(seed)

        prop()
