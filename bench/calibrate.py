"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 bench/calibrate.py --workload <name> --seeds 1,2,3 --seconds <s>

For each seed, in this one process: the cell's set-up, a short window at
the cell's own load, then the program's readings (what a run compares)
and the control's readings (the plain reference computed one precision
below the configuration's, in the program's place, on the same inputs).
A limit lies above every sound reading of the program and below every
reading of the control.  One JSON line per seed; the benchmark's own runs
never run the control.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    import jax
    from bench import harness as H
    cell = H.find_cell(ROOT, args.workload)
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        H.log(f"calibrate: {cell.name} needs {cell.chips} TPU chip(s)")
        return 2
    H.use_compile_cache(ROOT)
    peaks = H.peaks_for(ROOT, devs[0].device_kind)
    for seed in (int(s) for s in args.seeds.split(",")):
        ctx = H.Context(cell=cell, seed=seed, spans=H.Spans(), peaks=peaks,
                        interpret=False)
        state = cell.driver().setup(ctx)
        win = state.window(args.seconds)
        state.release()
        control = state.control()
        program = {k: v for k, (v, _) in state.check().items()}
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "attempted": win.attempted, "failed": win.failed,
                          "program": program, "control": control}), flush=True)
        del state
    return 0


if __name__ == "__main__":
    sys.exit(main())
