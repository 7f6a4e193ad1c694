"""A copy of the benchmark at sizes a CPU test can hold.

``tiny_root(tmp)`` copies ``BENCHMARK.json`` and ``bench/`` under ``tmp``
and shrinks every configuration and traffic file there; ``run_cell``
drives a whole run of one cell from that copy, past the harness's look
for a chip, and returns the result line.
"""
from __future__ import annotations

import io
import json
import shutil
import sys
from contextlib import redirect_stdout
from pathlib import Path

from bench import harness as H

REPO = H.ROOT
if str(REPO / "src") not in sys.path:       # the program, as run.py finds it
    sys.path.insert(0, str(REPO / "src"))
PEAKS = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}

JACOBI = {"ni": 40, "nj": 16, "nk": 128}
QWEN2 = {"hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
         "num_attention_heads": 4, "num_key_value_heads": 2,
         "vocab_size": 512, "limits": {"max_logit_gap": 0.01}}
STENCIL_TRAFFIC = {"sample_before": 3, "sample_rows": 4, "trace_seconds": 0.5}
SESSIONS = {"session_rate_per_s": 3.0, "first_prompt": {"median": 24, "sigma": 0.6},
            "followup_segment": {"median": 8, "sigma": 0.6}, "round_to": 16,
            "cap": 64, "output": {"median": 10, "sigma": 0.6, "min": 8, "max": 16},
            "think_mean_s": 0.2, "check_sample": 4, "drain_limit_s": 20,
            "trace_seconds": 1.0}


def _patch(path: Path, changes: dict) -> None:
    data = H.load_json(path)
    data.update(changes)
    path.write_text(json.dumps(data, indent=1))


def tiny_root(tmp: Path) -> Path:
    root = Path(tmp) / "checkout"
    (root / "bench").mkdir(parents=True)
    shutil.copy(REPO / "BENCHMARK.json", root)
    for sub in ("configs", "traffic", "drivers", "metrics"):
        shutil.copytree(REPO / "bench" / sub, root / "bench" / sub)
    shutil.copy(REPO / "bench" / "peaks.json", root / "bench")
    for cfg in (root / "bench" / "configs").glob("jacobi-*.json"):
        _patch(cfg, JACOBI)
    _patch(root / "bench" / "configs" / "qwen2-0.5b.json", QWEN2)
    _patch(root / "bench" / "traffic" / "kernel_sweeps.json", STENCIL_TRAFFIC)
    sessions = root / "bench" / "traffic" / "sessions.json"
    spec = H.load_json(sessions)["spec"]
    spec["serving"]["max_seq"] = 80      # cap + the longest answer
    _patch(sessions, dict(SESSIONS, spec=spec))
    manifest = H.load_json(root / "BENCHMARK.json")
    for w in manifest["workloads"]:
        w["chips"] = 1          # one CPU device
    (root / "BENCHMARK.json").write_text(json.dumps(manifest, indent=1))
    return root


def run_cell(root: Path, workload: str, seed: int = 7, seconds: float = 1.0,
             trace: int = 0) -> dict:
    """One whole run of ``workload`` from ``root`` on the CPU; JAX's
    compile-cache settings are put back afterwards, so other tests in the
    process see none of the benchmark's."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    run = H.load_module(REPO / "bench" / "run.py")
    saved = {k: getattr(jax.config, k) for k in H.CACHE_OPTIONS}
    out = io.StringIO()
    try:
        with redirect_stdout(out):
            rc = run.main(["--workload", workload, "--seed", str(seed),
                           "--seconds", str(seconds), "--trace", str(trace)],
                          require_chip=False, peaks=PEAKS, root=root)
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
    assert rc == 0, rc
    return json.loads(out.getvalue().strip().splitlines()[-1])
