"""Run one cell of the benchmark on the chip it is started on.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic and its metrics are named in
``BENCHMARK.json`` and found by ``bench/harness.py``.  The run refuses any
platform but a TPU, and fewer chips than the cell asks for, before any
work.  It makes its inputs and weights from ``--seed``, warms up every
shape the window uses (that is set-up), measures for ``--seconds``, then
frees the program's state and compares what the timed path produced with
the plain reference.  The numbers compared, each beside its limit, are the
last lines on standard error and the ``checks`` key of the result.  The
last line on standard output is the result as one JSON object.

With ``--trace 1`` the window runs under the profiler, for the traffic's
``trace_seconds`` at most, and the result carries the per-layer metrics
read from the trace and the host spans, with a breakdown.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, *, require_chip: bool = True, peaks: dict | None = None,
         root: Path = ROOT, t_start: float = T0) -> int:
    args = parse(argv)
    from bench import harness as H
    from bench import xplane

    cell = H.find_cell(root, args.workload)
    import jax
    devs = jax.devices()
    if require_chip and (devs[0].platform != "tpu" or len(devs) < cell.chips):
        H.log(f"bench: {cell.name} needs {cell.chips} TPU chip(s); JAX "
              f"found {len(devs)} {devs[0].platform!r} device(s)")
        return 2
    H.use_compile_cache(root)
    counter = H.CompileCounter()
    devices = devs[:cell.chips]
    if peaks is None:
        peaks = H.peaks_for(root, devs[0].device_kind)
    ctx = H.Context(cell=cell, seed=args.seed, spans=H.Spans(bool(args.trace)),
                    peaks=peaks,
                    interpret=devs[0].platform != "tpu")

    state = cell.driver().setup(ctx)
    setup_s = time.perf_counter() - t_start
    seconds = args.seconds
    if args.trace:
        seconds = min(seconds, float(cell.traffic.get("trace_seconds", seconds)))
    print(f"setup_s={setup_s!r} window_s={seconds!r}", flush=True)

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if args.trace else None
    try:
        counter.armed = True
        if trace_dir:
            jax.profiler.start_trace(trace_dir)
        with ctx.spans("bench.window"):
            win = state.window(seconds)
        if trace_dir:
            jax.profiler.stop_trace()
        counter.armed = False
        print(f"compiles_in_window={counter.count}", flush=True)
        peak = H.memory_peak_bytes(devices)
        state.release()
        checks = state.check()
        trace = xplane.load(trace_dir, len(devices)) if trace_dir else None
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    if args.trace:
        metrics = {}
        for m in cell.metrics("per_layer"):
            v = cell.reader(m["name"]).read(ctx, win, trace)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        values = dict(win.metrics, setup_s=setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.metrics("end_to_end")}

    correct = win.failed == 0 and all(v <= lim for v, lim in checks.values())
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": win.attempted,
              "failed": win.failed, "metrics": metrics, "device": device}
    if trace is not None:
        device["busy_s"] = trace.busy_s()
        device["window_s"] = trace.window_s
        result["breakdown"] = {"device_ops": trace.top_ops(10),
                               "idle_gaps": trace.idle_gaps(10)}
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    for k, (v, lim) in checks.items():
        H.log(f"check {k} = {v!r} (limit {lim!r})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
