"""The program-span metrics: the readers of each serving request's own
timeline (``Request.timing``) on the tiny CPU cell, the map from the host
clock onto the trace's, checked on a synthetic trace and against the
benchmark's own spans in a real profiler trace, and the four-chip cell's
halo share on a synthetic trace."""
from __future__ import annotations

import glob
from types import SimpleNamespace

import jax
import pytest

from bench import harness as H
from bench import timeline
from bench.tests import tiny
from bench.xplane import Device, Event, Trace

READERS = ["sched_wait_p90_ms.serve", "prefill_p90_ms.serve",
           "decode_dispatch_us.serve", "decode_sample_us.serve",
           "decode_fetch_us.serve", "idle_in_grab_pct.serve"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.tiny_root(tmp_path_factory.mktemp("spans"))


def _host_events(path: str):
    """The events of the host's Python thread, and the XLA operations of
    the CPU's threads standing in for a device's."""
    from jax.profiler import ProfileData
    python, ops = [], []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for ln in plane.lines:
            for e in ln.events:
                a, b = e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9
                if ln.name == "python":
                    python.append(Event(e.name, a, b))
                elif ln.name.startswith("tf_XLA") and "::" not in e.name:
                    ops.append(Event(e.name, a, b))
    return python, ops


@pytest.fixture(scope="module")
def traced(root, tmp_path_factory):
    """The tiny serve cell's window run by hand as ``bench/run.py`` runs it
    under the profiler, with the trace kept."""
    cell = H.find_cell(root, "serve_sessions")
    ctx = H.Context(cell=cell, seed=11, spans=H.Spans(True), peaks=tiny.PEAKS,
                    interpret=True)
    state = cell.driver().setup(ctx)
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(trace_dir)
    with ctx.spans("bench.window"):
        win = state.window(2.0)
    jax.profiler.stop_trace()
    path = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)[0]
    python, ops = _host_events(path)
    bench = [e for e in python if e.name.startswith("bench.")]
    trace = Trace([Device("cpu", ops, [])], bench)
    return ctx, win, trace


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_the_tiny_serve_cell(root, traced, name):
    ctx, win, trace = traced
    value = H.find_cell(root, "serve_sessions").reader(name).read(ctx, win, trace)
    assert value is not None and value > 0, value
    if name == "idle_in_grab_pct.serve":
        idle = 100.0 * trace.idle_share()
        assert value <= idle + 1e-9


def test_sched_wait_agrees_with_the_grab_start_wait(traced):
    ctx, win, _ = traced
    starts = win.facts["grab_start"]
    waits = [(starts[r.uid] - r.timing.t_submit) * 1e3
             for _, r in win.facts["turns"]]
    got = timeline.p90_ms(win, "t_submit", "t_grab")
    assert abs(got - H.percentile(waits, 90)) <= 1.0


def test_decode_readers_sum_to_the_mean_gap_between_tokens(traced):
    _, win, _ = traced
    gaps = tokens = 0.0
    for _, r in win.facts["turns"]:
        times = r.out_tokens.times
        gaps += times[-1] - times[0]
        tokens += len(times) - 1
    parts = sum(timeline.per_decode_token_us(win, f)
                for f in ("dispatch_s", "sample_s", "fetch_s"))
    assert parts == pytest.approx(1e6 * gaps / tokens, rel=0.05)


def test_timeline_stamps_map_onto_the_trace_clock(traced):
    """The engine stamps ``t_grab`` just before the benchmark's wrapper of
    ``run_batch`` opens its ``bench.replica_grab`` span: mapped by the
    window's two ends, each request's grab and last token fall on that
    span in the profiler's own record."""
    ctx, win, trace = traced
    to_trace = timeline.clock_map(ctx.spans.records["bench.window"][0],
                                  trace.window)
    grabs = [s for s in trace.spans if s.name == "bench.replica_grab"]
    assert grabs
    for _, r in win.facts["turns"]:
        g0 = to_trace(r.timing.t_grab)
        span = min(grabs, key=lambda s: abs(s.start - g0))
        assert abs(span.start - g0) < 2e-3
        assert span.start < to_trace(r.timing.t_last) <= span.end + 2e-3


def _timed(uid, t_grab, t_last):
    return SimpleNamespace(uid=uid, out_tokens=[0, 0],
                           timing=SimpleNamespace(t_grab=t_grab, t_last=t_last))


def test_clock_map_and_idle_in_grab_on_a_synthetic_trace():
    # the trace's clock runs at twice the host's, 100 s ahead
    to_trace = timeline.clock_map((10.0, 20.0), (120.0, 140.0))
    assert to_trace(10.0) == 120.0 and to_trace(15.0) == 130.0
    spans = H.Spans()
    spans.records["bench.window"] = [(10.0, 20.0)]
    ctx = SimpleNamespace(spans=spans)
    # grabs at host 11-13 and 12-14 (trace 122-128), 17-19 (trace 134-138)
    turns = [(None, _timed(0, 11.0, 13.0)), (None, _timed(1, 12.0, 14.0)),
             (None, _timed(2, 17.0, 19.0))]
    win = H.Window(seconds=10.0, attempted=3, failed=0, metrics={},
                   facts={"turns": turns})
    ops = [Event("%a fusion", 121.0, 125.0), Event("%b fusion", 135.0, 136.0)]
    trace = Trace([Device("/device:TPU:0", ops, [])],
                  [Event("bench.window", 120.0, 140.0)])
    # in grabs 6 + 4 = 10 s, busy inside them 3 + 1 = 4 s: 6 s of 20
    assert timeline.idle_in_grab_share(ctx, win, trace) == pytest.approx(0.3)


def test_a_program_without_timelines_gives_nothing_to_read():
    bare = SimpleNamespace(uid=0, out_tokens=[0, 0])
    win = H.Window(seconds=1.0, attempted=1, failed=0, metrics={},
                   facts={"turns": [(None, bare)]})
    assert timeline.p90_ms(win, "t_submit", "t_grab") is None
    assert timeline.per_decode_token_us(win, "fetch_s") is None
    assert timeline.idle_in_grab_share(None, win, None) is None


def test_traced_run_line_reports_the_program_span_metrics(root):
    out = tiny.run_cell(root, "serve_sessions", seed=2**33 + 9, seconds=2.0,
                        trace=1)
    assert out["correct"], out
    # the CPU trace holds no device plane: the device readers stay silent
    for name in READERS[:-1]:
        assert out["metrics"][name]["value"] > 0, name
    assert "idle_in_grab_pct.serve" not in out["metrics"]


def test_halo_share_counts_only_the_collective_permutes(root):
    read = H.find_cell(root, "jacobi_spmd_4chip").reader(
        "collective_share.spmd").read
    ops = [Event("%fusion.6 fusion", 0.0, 3.0),
           Event("%collective-permute-start collective-permute-start", 3.0, 3.1),
           Event("%collective-permute-done collective-permute-done", 3.1, 3.5),
           Event("%all-gather.5 all-gather", 5.0, 6.0)]
    spans = [Event("bench.window", 0.0, 10.0)]
    trace = Trace([Device("/device:TPU:0", ops, []),
                   Device("/device:TPU:1", [Event("%fusion.6 fusion", 0.0, 4.0)],
                          [])], spans)
    # device 0: 0.5 s of halo in 4.5 s busy (the all-gather counts as busy
    # only); device 1 exchanges nothing
    assert read(None, None, trace) == pytest.approx(100.0 * (0.5 / 4.5) / 2)
    trace.devices = trace.devices[1:]
    assert read(None, None, trace) is None
    assert read(None, None, None) is None
