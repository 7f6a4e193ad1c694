"""Property-based tests (hypothesis) for the scheduling invariants."""
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import (ISTANBUL, NEHALEM_EP, BlockGrid,
                        OpenMPLocalityQueues, OpenMPTasking,
                        StaticWorksharing, TBBLocalityQueues,
                        build_assignment, maxmin_rates, place,
                        round_robin_assignment, simulate)
from repro.runtime import DomainQueues

TOPOS = [ISTANBUL, NEHALEM_EP]


@st.composite
def small_grids(draw):
    bi = draw(st.integers(2, 6))
    bj = draw(st.integers(2, 6))
    di = draw(st.sampled_from([2, 5]))
    dj = draw(st.sampled_from([2, 5]))
    return BlockGrid(ni=bi * di, nj=bj * dj, nk=16, di=di, dj=dj, dk=16)


class TestSimulatorConservation:
    @settings(max_examples=12, deadline=None)
    @given(grid=small_grids(),
           policy_kind=st.sampled_from(["static_ws", "omp_task", "omp_lq",
                                        "tbb_lq"]),
           placement=st.sampled_from(["serial", "static", "static1",
                                      "round_robin"]),
           order=st.sampled_from(["ijk", "kji"]),
           seed=st.integers(0, 5))
    def test_every_block_executed_exactly_once(self, grid, policy_kind,
                                               placement, order, seed):
        topo = NEHALEM_EP
        homes = place(placement, grid, topo, order="ijk")
        executed = []

        if policy_kind == "static_ws":
            pol = StaticWorksharing()
        elif policy_kind == "omp_task":
            pol = OpenMPTasking(submit_order=order, pool_cap=16)
        elif policy_kind == "tbb_lq":
            pol = TBBLocalityQueues()
        else:
            pol = OpenMPLocalityQueues(submit_order=order, pool_cap=16)

        orig_pop = pol.pop

        def spy_pop(thread):
            got = orig_pop(thread)
            if got is not None:
                executed.append(got.block)
            return got

        pol.pop = spy_pop
        r = simulate(grid, topo, pol, homes, seed=seed)
        assert sorted(executed) == list(range(grid.num_blocks))
        assert r.makespan_s > 0

    @settings(max_examples=10, deadline=None)
    @given(grid=small_grids(), seed=st.integers(0, 3))
    def test_locality_queue_steals_only_when_local_empty(self, grid, seed):
        topo = ISTANBUL
        q = DomainQueues(topo.num_domains, steal_order="cyclic")
        rng = np.random.default_rng(seed)
        homes = rng.integers(0, topo.num_domains, grid.num_blocks)
        for blk in range(grid.num_blocks):
            q.enqueue(blk, int(homes[blk]))
        for ld in range(topo.num_domains):
            local_size = q.queue_sizes()[ld]      # live, pre-dequeue
            got = q.dequeue(ld)
            assert got is not None
            blk, stolen = got.item, got.stolen
            if local_size > 0:
                assert not stolen and homes[blk] == ld
            else:
                assert stolen and homes[blk] != ld


class TestAssignmentBuilder:
    @settings(max_examples=25, deadline=None)
    @given(n=st.integers(4, 200), d=st.integers(2, 16),
           seed=st.integers(0, 10),
           imb=st.floats(0.01, 0.3))
    def test_partition_and_balance(self, n, d, seed, imb):
        rng = np.random.default_rng(seed)
        homes = rng.integers(-1, d, size=n)
        cost = rng.uniform(0.5, 2.0, size=n)
        a = build_assignment(homes, cost, d, max_imbalance=imb)
        # every task exactly once
        all_tasks = sorted(t for lst in a.lists for t in lst)
        assert all_tasks == list(range(n))
        # loads consistent
        for dd in range(d):
            assert abs(a.loads[dd] - sum(cost[t] * 1.0 for t in a.lists[dd])) \
                < 1e-6 + 0.3 * a.loads[dd]  # remote_penalty may inflate loads
        assert 0.0 <= a.locality_fraction <= 1.0

    @settings(max_examples=15, deadline=None)
    @given(n=st.integers(8, 100), d=st.integers(2, 8), seed=st.integers(0, 5))
    def test_locality_beats_round_robin(self, n, d, seed):
        rng = np.random.default_rng(seed)
        homes = rng.integers(0, d, size=n)
        cost = np.ones(n)
        a = build_assignment(homes, cost, d)
        rr = round_robin_assignment(n, cost, d)
        assert a.locality_fraction >= rr.locality_fraction

    def test_stealing_bounds_imbalance(self):
        # pathological: everything homed in domain 0
        n, d = 64, 4
        homes = np.zeros(n, dtype=np.int64)
        cost = np.ones(n)
        a = build_assignment(homes, cost, d, max_imbalance=0.1)
        assert a.imbalance <= 0.15
        assert a.moved > 0
        assert a.locality_fraction < 1.0   # balance was bought with locality


class TestCostModel:
    @settings(max_examples=20, deadline=None)
    @given(f=st.integers(1, 30), seed=st.integers(0, 20))
    def test_rates_respect_capacities(self, f, seed):
        topo = ISTANBUL
        rng = np.random.default_rng(seed)
        home = rng.integers(-1, topo.num_domains, size=f)
        exec_ld = rng.integers(0, topo.num_domains, size=f)
        rates = maxmin_rates(home, exec_ld, topo)
        assert (rates > 0).all()
        # per-flow cap
        assert (rates <= topo.core_bw + 1e-9).all()
        # per-bus capacity
        for l in range(topo.num_domains):
            w = np.where(home == l, 1.0, 0.0) + np.where(home == -1,
                                                         1.0 / topo.num_domains, 0.0)
            assert float(w @ rates) <= topo.local_bw * (1 + 1e-9)
