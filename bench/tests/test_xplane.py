"""The trace reduction on synthetic traces, and the parse of a recorded one."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from bench import harness as H
from bench import xplane
from bench.xplane import Device, Event, Trace


def _trace():
    ops = [Event("%a fusion", 0.0, 1.0), Event("%b copy", 0.5, 2.0),
           Event("%a fusion", 3.0, 4.0), Event("%cp collective-permute", 4.0, 4.5),
           Event("%a fusion", 9.5, 11.0)]
    mods = [Event("jit_step(1)", 0.0, 2.0), Event("jit_step(1)", 3.0, 4.5),
            Event("jit_step(1)", 9.5, 11.0)]
    spans = [Event("bench.window", 0.0, 10.0), Event("bench.dispatch", 2.0, 2.9),
             Event("bench.wait", 4.5, 9.0), Event("bench.step", 4.0, 9.5)]
    other = Device("/device:TPU:1", [Event("%a fusion", 0.0, 5.0)], [])
    return Trace([Device("/device:TPU:0", ops, mods), other], spans)


def test_busy_is_the_union_clipped_to_the_window():
    t = _trace()
    assert t.window_s == 10.0
    assert t.busy_intervals(t.devices[0]) == [(0.0, 2.0), (3.0, 4.5), (9.5, 10.0)]
    assert t.busy_s() == pytest.approx((4.0 + 5.0) / 2)
    assert t.idle_share() == pytest.approx(1 - 4.5 / 10)


def test_top_ops_are_means_over_devices():
    top = dict(t for t in _trace().top_ops(10))
    assert top["%a fusion"] == pytest.approx((2.5 + 5.0) / 2)
    assert top["%b copy"] == pytest.approx(1.5 / 2)


def test_idle_gaps_are_named_by_the_innermost_host_span():
    gaps = _trace().idle_gaps(10)
    assert gaps[0] == ["bench.wait", pytest.approx(5.0)]
    assert gaps[1] == ["bench.dispatch", pytest.approx(1.0)]


def test_matching_keeps_only_events_wholly_in_the_window():
    t = _trace()
    assert len(t.matching_ops(t.devices[0], r"^%a fusion$")) == 2
    assert len(t.matching_modules(t.devices[0], r"jit_step")) == 2


def test_collective_share_and_its_absence():
    t = _trace()
    assert t.collective_share() == pytest.approx((0.5 / 4.0 + 0.0) / 2)
    t.devices = t.devices[1:]
    assert t.collective_share() is None


def test_no_device_means_no_idle_share():
    t = Trace([], [Event("bench.window", 0.0, 1.0)])
    assert t.idle_share() is None and t.idle_gaps() == [] and t.busy_s() == 0.0


def test_op_names_are_shortened_from_hlo_text():
    hlo = ("%jacobi_sweep_pallas.1 = f32[2400,600,600]{2,1,0:T(8,128)} "
           "custom-call(f32[1]{0:T(128)} %bitcast.1), custom_call_target=\"x\"")
    assert xplane.op_name(hlo) == "%jacobi_sweep_pallas.1 custom-call"
    assert xplane.op_name("jit_step(123)") == "jit_step(123)"


def test_parse_finds_the_bench_spans_of_a_recorded_trace(tmp_path):
    spans = H.Spans(annotate=True)
    f = jax.jit(lambda x: x * 2)
    f(jnp.ones(4)).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with spans("bench.window"):
        with spans("bench.dispatch"):
            f(jnp.ones(4)).block_until_ready()
    jax.profiler.stop_trace()
    t = xplane.load(str(tmp_path), 1)
    names = {s.name for s in t.spans}
    assert {"bench.window", "bench.dispatch"} <= names
    assert t.window_s > 0 and t.devices == []
