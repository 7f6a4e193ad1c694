"""Paper-claim validation: the discrete-event simulator must reproduce the
qualitative results of Fig. 3 / Fig. 4 and Table 1 on all three test-bed
systems."""
import numpy as np
import pytest

from repro.core import (ISTANBUL, NEHALEM_EP, NEHALEM_EX, SMALL_GRID, TESTBED,
                        BlockGrid, OpenMPLocalityQueues, OpenMPTasking, StaticWorksharing,
                        TBBLocalityQueues, TBBParallelFor, place, run_samples,
                        simulate, stream_sanity, summarize, tbb_first_touch)


def _ws(topo, placement, seed=0):
    homes = place(placement, SMALL_GRID, topo)
    return simulate(SMALL_GRID, topo, StaticWorksharing(), homes, seed=seed)


@pytest.mark.parametrize("topo", [ISTANBUL, NEHALEM_EP, NEHALEM_EX],
                         ids=lambda t: t.name)
class TestReferenceLines:
    """The three horizontal lines of Fig. 3 (per system)."""

    def test_ordering_serial_rr_firsttouch(self, topo):
        serial = _ws(topo, "serial").mlups
        rr = _ws(topo, "round_robin").mlups
        ft = _ws(topo, "static").mlups
        assert serial < rr < ft, (serial, rr, ft)

    def test_first_touch_matches_stream(self, topo):
        """Optimal placement comes close to the STREAM envelope (§1.4)."""
        ft = _ws(topo, "static")
        from repro.core import block_bytes, bytes_per_site
        stream_mlups = topo.full_bw * 1e9 / bytes_per_site(topo.nt_stores) / 1e6
        assert ft.mlups > 0.9 * stream_mlups
        assert ft.local_fraction == 1.0

    def test_serial_is_single_domain_bound(self, topo):
        serial = _ws(topo, "serial")
        from repro.core import bytes_per_site
        one_ld_mlups = topo.local_bw * 1e9 / bytes_per_site(topo.nt_stores) / 1e6
        assert serial.mlups <= 1.02 * one_ld_mlups


@pytest.mark.parametrize("topo", [NEHALEM_EP, ISTANBUL], ids=lambda t: t.name)
class TestOpenMPTasking:
    """Fig. 3 columns 1–2: plain tasking vs locality queues."""

    def test_plain_tasking_never_beats_round_robin(self, topo):
        """Paper §2.1: 'this code is never faster than standard worksharing
        with round-robin placement'."""
        rr = _ws(topo, "round_robin").mlups
        for init in ("static", "static1"):
            for order in ("ijk", "kji"):
                homes = place(init, SMALL_GRID, topo)
                r = simulate(SMALL_GRID, topo,
                             OpenMPTasking(submit_order=order), homes, seed=1)
                assert r.mlups <= 1.08 * rr, (init, order, r.mlups, rr)

    def test_static_ijk_especially_unfortunate(self, topo):
        """Paper §2.1: static init + ijk submit order is the worst combo."""
        results = {}
        for init in ("static", "static1"):
            for order in ("ijk", "kji"):
                homes = place(init, SMALL_GRID, topo)
                r = simulate(SMALL_GRID, topo,
                             OpenMPTasking(submit_order=order), homes, seed=1)
                results[(init, order)] = r.mlups
        assert results[("static", "ijk")] == min(results.values())

    def test_locality_queues_recover_static_performance(self, topo):
        """Paper §2.2: with kji order or static,1 init, locality queues come
        within 10% of static first-touch worksharing."""
        ft = _ws(topo, "static").mlups
        for init, order in [("static", "kji"), ("static1", "ijk"),
                            ("static1", "kji")]:
            homes = place(init, SMALL_GRID, topo)
            r = simulate(SMALL_GRID, topo,
                         OpenMPLocalityQueues(submit_order=order), homes, seed=1)
            assert r.mlups > 0.9 * ft, (init, order, r.mlups, ft)
            assert r.local_fraction > 0.95

    def test_locality_queues_static_ijk_still_poor(self, topo):
        """Paper §2.2: static+ijk starves all but one queue (the 256-task cap
        keeps the submission window inside a single domain)."""
        ft = _ws(topo, "static").mlups
        homes = place("static", SMALL_GRID, topo)
        r = simulate(SMALL_GRID, topo, OpenMPLocalityQueues(submit_order="ijk"),
                     homes, seed=1)
        assert r.mlups < 0.75 * ft
        assert r.steal_fraction > 0.1


class TestHomelessBlocks:
    """``round_robin`` placement homes every block at -1 (pages interleaved
    over every domain); the locality-queue policies queue such a block on
    domain ``blk % num_domains``."""

    @pytest.mark.parametrize("make", [
        lambda: OpenMPLocalityQueues(submit_order="ijk"),
        lambda: OpenMPLocalityQueues(submit_order="kji"),
        TBBLocalityQueues,
    ], ids=["omp_lq_ijk", "omp_lq_kji", "tbb_lq"])
    def test_every_block_executed_once_interleaved(self, make):
        grid = BlockGrid(ni=8, nj=8, nk=16, di=2, dj=2, dk=16)   # 4x4 blocks
        topo = NEHALEM_EP
        homes = place("round_robin", grid, topo)
        assert (homes == -1).all()
        pol = make()
        executed = []
        orig_pop = pol.pop

        def spy_pop(thread):
            got = orig_pop(thread)
            if got is not None:
                executed.append(got.block)
            return got

        pol.pop = spy_pop
        r = simulate(grid, topo, pol, homes, seed=0)
        assert sorted(executed) == list(range(grid.num_blocks))
        assert r.makespan_s > 0

    def test_interleaved_block_queued_by_block_id(self):
        from repro.core.scheduler import queue_domain
        assert [queue_domain(b, -1, 4) for b in range(6)] == [0, 1, 2, 3, 0, 1]
        assert queue_domain(5, 2, 4) == 2


class TestTBB:
    """Fig. 3 columns 3–4."""

    def test_affinity_partitioner_restores_locality(self):
        topo = ISTANBUL
        rng = np.random.default_rng(7)
        homes, threads = tbb_first_touch(SMALL_GRID, topo, rng)
        aff = simulate(SMALL_GRID, topo,
                       TBBParallelFor(affinity=True, replay=threads),
                       homes, seed=7)
        noaff = simulate(SMALL_GRID, topo, TBBParallelFor(affinity=False),
                         homes, seed=7)
        ft = _ws(topo, "static").mlups
        assert aff.mlups > 0.95 * ft
        assert noaff.mlups < 0.85 * aff.mlups

    def test_tbb_locality_queues_marginal_over_affinity(self):
        """Paper §3.2: TBB+LQ does not outperform the affinity partitioner."""
        topo = ISTANBUL
        rng = np.random.default_rng(7)
        homes, threads = tbb_first_touch(SMALL_GRID, topo, rng)
        aff = simulate(SMALL_GRID, topo,
                       TBBParallelFor(affinity=True, replay=threads),
                       homes, seed=7)
        lq = simulate(SMALL_GRID, topo, TBBLocalityQueues(), homes, seed=7)
        assert abs(lq.mlups - aff.mlups) / aff.mlups < 0.1

    def test_unpinned_affinity_degrades(self):
        topo = NEHALEM_EP
        rng = np.random.default_rng(3)
        homes, threads = tbb_first_touch(SMALL_GRID, topo, rng)
        pinned = simulate(SMALL_GRID, topo,
                          TBBParallelFor(affinity=True, replay=threads),
                          homes, seed=3, pinned=True)
        unpinned = simulate(SMALL_GRID, topo,
                            TBBParallelFor(affinity=True, replay=threads),
                            homes, seed=3, pinned=False)
        assert unpinned.local_fraction < pinned.local_fraction


class TestVariabilityFig4:
    def test_variability_is_small(self):
        """Fig. 4: run-to-run quantile spread is a few percent."""
        topo = NEHALEM_EP
        homes = place("static1", SMALL_GRID, topo)
        res = run_samples(SMALL_GRID, topo,
                          lambda: OpenMPLocalityQueues(submit_order="kji"),
                          homes, n_samples=9)
        s = summarize(res)
        spread = (s["q75"] - s["q25"]) / s["median_mlups"]
        assert spread < 0.08


class TestStreamTable1:
    @pytest.mark.parametrize("name", list(TESTBED))
    def test_model_matches_table1(self, name):
        topo = TESTBED[name]
        s = stream_sanity(topo)
        # full-machine local bandwidth ≈ Table 1 full-system STREAM (±10%)
        table1_full = {"istanbul": 38.6, "nehalem_ep": 36.6, "nehalem_ex": 33.4}
        assert abs(s["full_local_bw"] - table1_full[name]) / table1_full[name] < 0.1
        # serial placement saturates exactly one socket
        assert abs(s["serial_ld0_bw"] - topo.local_bw) / topo.local_bw < 0.05
        assert s["serial_ld0_bw"] < s["interleaved_bw"] < s["full_local_bw"]
