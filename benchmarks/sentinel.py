"""BENCH regression sentinel: fresh runs vs the committed baselines.

    PYTHONPATH=src python -m benchmarks.sentinel [--only a,b] [--full]

The BENCH_*.json artifacts were write-only — refreshed by whoever last ran
the benchmarks, drifting silently otherwise.  This gate (``make sentinel``)
gives them teeth: it re-runs each benchmark *at the committed baseline's
own declared parameters*, compares every numeric metric under a per-metric
tolerance policy, writes a markdown report (``BENCH_sentinel.md``), appends
a summary entry to the ``BENCH_trajectory.json`` history, and exits nonzero
on any regression — the before/after scoreboard ROADMAP item 2's hot-path
rewrite is graded by.

Tolerance policy (``metric_policy``) — the load-bearing design choice:

  * **deterministic metrics** (control/topology/experiments: every count,
    fraction, penalty, percentile; overhead: the ``stats_identical`` gate)
    come off the seeded step-clock simulator, so they are bit-reproducible
    across machines.  Tolerance: *exact* — any delta is drift and fails.
    This is what makes the sentinel schedule-passive: it asserts the
    schedule, it never perturbs it.
  * **wall-clock metrics** (overhead: ``ns_per_decision.*``) are machine-
    dependent.  They gate *lower-is-better* with a deliberately loose 3x
    ratio — wide enough that a shared CI box never flakes, tight enough to
    catch an accidental O(n) slip in a hot path.  Pure environment
    readouts (``wall_*``, ``tasks_per_s``, ``overhead_frac`` — already
    gated inside the benchmark itself, ``repeats_used``,
    ``profile_total_ns``) are reported but never gated here.
  * metrics present in the baseline but missing fresh fail (a deleted
    measurement is a regression of the record); new fresh metrics are
    reported as ``new`` and pass (the next baseline refresh adopts them).

Fresh runs write to a temp directory — the committed BENCH baselines are
never clobbered by the sentinel (refreshing a baseline stays an explicit
``make bench-*`` + commit).  ``--only`` restricts the bench set;
``--full`` runs the overhead bench's full task ladder instead of the fast
CI ladder (rows are compared on the (n_tasks, num_domains) intersection
either way).
"""
from __future__ import annotations

import dataclasses
import json
import os
import sys
import tempfile
import time
from typing import Any, Optional

BASELINES = {
    "control": "BENCH_control.json",
    "topology": "BENCH_topology.json",
    "overhead": "BENCH_overhead.json",
    "experiments": "BENCH_experiments.json",
}
REPORT_PATH = "BENCH_sentinel.md"
TRAJECTORY_PATH = "BENCH_trajectory.json"

EXACT_EPS = 1e-9          # float equality slack for deterministic metrics
WALL_RATIO_TOL = 2.0      # lower-better wall metrics may grow up to 3x

# wall-clock environment readouts: reported, never gated
_UNGATED = ("wall_off_s", "wall_on_s", "tasks_per_s", "overhead_frac",
            "profile_total_ns", "repeats_used")


@dataclasses.dataclass(frozen=True)
class Finding:
    """One compared metric: baseline vs fresh under its policy."""

    bench: str
    metric: str
    baseline: Optional[float]
    fresh: Optional[float]
    direction: str            # "equal" | "lower" | "info"
    status: str               # "ok" | "regression" | "improvement"
                              # | "new" | "missing" | "info"

    @property
    def failed(self) -> bool:
        return self.status in ("regression", "missing")


def flatten(obj: Any, prefix: str = "") -> dict[str, float]:
    """Numeric leaves of a nested JSON value as dotted paths (lists index
    as ``[i]``; booleans and the embedded ``experiment`` spec blocks are
    config, not measurements, and are skipped)."""
    out: dict[str, float] = {}
    if isinstance(obj, bool):
        return out
    if isinstance(obj, (int, float)):
        out[prefix] = float(obj)
        return out
    if isinstance(obj, dict):
        for k in sorted(obj):
            if k == "experiment":
                continue
            out.update(flatten(obj[k], f"{prefix}.{k}" if prefix else k))
        return out
    if isinstance(obj, list):
        for i, v in enumerate(obj):
            out.update(flatten(v, f"{prefix}[{i}]"))
    return out


def metric_policy(bench: str, path: str) -> str:
    """``"equal"`` (deterministic — exact), ``"lower"`` (wall, loose
    lower-is-better), or ``"info"`` (reported, never gated)."""
    leaf = path.rsplit(".", 1)[-1].split("[")[0]
    if leaf in _UNGATED:
        return "info"
    if bench == "overhead" and ".ns_per_decision." in f".{path}":
        return "lower"
    return "equal"


def _compare_one(bench: str, path: str, base: Optional[float],
                 fresh: Optional[float]) -> Finding:
    direction = metric_policy(bench, path)
    if base is None:
        return Finding(bench, path, None, fresh, direction, "new")
    if fresh is None:
        return Finding(bench, path, base, None, direction,
                       "info" if direction == "info" else "missing")
    if direction == "info":
        return Finding(bench, path, base, fresh, direction, "info")
    if direction == "lower":
        if fresh > base * (1.0 + WALL_RATIO_TOL):
            status = "regression"
        elif fresh < base:
            status = "improvement"
        else:
            status = "ok"
        return Finding(bench, path, base, fresh, direction, status)
    # exact: any drift beyond float-formatting noise fails
    ok = abs(fresh - base) <= EXACT_EPS * max(1.0, abs(base), abs(fresh))
    return Finding(bench, path, base, fresh, direction,
                   "ok" if ok else "regression")


def compare(baseline: dict, fresh: dict, bench: str) -> list[Finding]:
    """Per-metric findings over the union of flattened numeric paths."""
    fb, ff = flatten(baseline), flatten(fresh)
    return [_compare_one(bench, path, fb.get(path), ff.get(path))
            for path in sorted(set(fb) | set(ff))]


# -- fresh runs (at the baseline's own declared parameters) -------------------

def _run_control(base: dict, out: str) -> None:
    from benchmarks import control_plane
    control_plane.main(steps=base.get("steps", 48), seed=base.get("seed", 0),
                       json_path=out)


def _run_topology(base: dict, out: str) -> None:
    from benchmarks import topology_locality
    topology_locality.main(steps=base.get("steps", 48),
                           seed=base.get("seed", 0), json_path=out)


def _overhead_rows(base: dict, out: str, full: bool) -> None:
    from benchmarks import scheduler_overhead as so
    if full:
        scales, domains = so.TASK_SCALES, so.DOMAIN_SCALES
    else:
        scales, domains = so.FAST_TASK_SCALES, so.FAST_DOMAIN_SCALES
    so.main(task_scales=scales, domain_scales=domains,
            repeats=base.get("repeats", 5), json_path=out)


def _run_experiments(base: dict, out: str) -> None:
    from benchmarks.run import _cli_experiments, run_experiments
    experiments, _ = _cli_experiments(["--experiment", "all"])
    run_experiments(experiments, json_path=out)


def _intersect_overhead(base: dict, fresh: dict) -> tuple[dict, dict]:
    """Restrict both overhead results to the shared (n_tasks, num_domains)
    rows, re-keyed by configuration so row order can't misalign the diff
    (the fast CI ladder runs a subset of the committed full ladder)."""
    def rows(d):
        return {f"{r['n_tasks']}x{r['num_domains']}": r
                for r in d.get("results", [])}
    nb = {k: v for k, v in base.items() if k != "results"}
    nf = {k: v for k, v in fresh.items() if k != "results"}
    rb, rf = rows(base), rows(fresh)
    shared = sorted(set(rb) & set(rf))
    nb["rows"] = {k: rb[k] for k in shared}
    nf["rows"] = {k: rf[k] for k in shared}
    return nb, nf


# -- report + trajectory ------------------------------------------------------

def render_report(all_findings: dict[str, list[Finding]],
                  skipped: dict[str, str]) -> str:
    """The markdown regression report (``BENCH_sentinel.md``): verdict,
    per-bench summary, every non-ok finding in full."""
    from repro.obs.report import markdown_table

    failed = [f for fs in all_findings.values() for f in fs if f.failed]
    lines = ["# BENCH regression sentinel", "",
             ("**FAIL** — regression against committed baselines."
              if failed else
              "**PASS** — no regression against committed baselines."), "",
             markdown_table(
                 ["bench", "metrics", "ok", "regressions", "improvements",
                  "new", "info"],
                 [[b, len(fs),
                   sum(1 for f in fs if f.status == "ok"),
                   sum(1 for f in fs if f.failed),
                   sum(1 for f in fs if f.status == "improvement"),
                   sum(1 for f in fs if f.status == "new"),
                   sum(1 for f in fs if f.status == "info")]
                  for b, fs in sorted(all_findings.items())])]
    for bench, reason in sorted(skipped.items()):
        lines.append(f"\n(skipped `{bench}`: {reason})")
    notable = [f for fs in all_findings.values() for f in fs
               if f.status not in ("ok", "info")]
    if notable:
        lines += ["", "## Non-ok findings", "",
                  markdown_table(
                      ["bench", "metric", "baseline", "fresh", "policy",
                       "status"],
                      [[f.bench, f.metric,
                        "-" if f.baseline is None else f"{f.baseline:g}",
                        "-" if f.fresh is None else f"{f.fresh:g}",
                        f.direction, f.status] for f in notable])]
    return "\n".join(lines) + "\n"


def append_trajectory(all_findings: dict[str, list[Finding]],
                      path: str = TRAJECTORY_PATH) -> dict:
    """Append this run's summary to the BENCH history file (created on
    first run) and return the entry."""
    entry = {
        "when": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "ok": not any(f.failed for fs in all_findings.values() for f in fs),
        "benches": {b: {"metrics": len(fs),
                        "regressions": sum(1 for f in fs if f.failed),
                        "improvements": sum(1 for f in fs
                                            if f.status == "improvement")}
                    for b, fs in sorted(all_findings.items())},
    }
    history = {"bench": "sentinel_trajectory", "entries": []}
    if os.path.exists(path):
        with open(path, "r", encoding="utf-8") as fh:
            history = json.load(fh)
    history["entries"].append(entry)
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(history, fh, indent=2)
        fh.write("\n")
    return entry


RUNNERS = {
    "control": _run_control,
    "topology": _run_topology,
    "experiments": _run_experiments,
}

# traces committed to the repo: the model checker must hold on all of them
MODEL_FIXTURES = ("tests/data/v1_trace_fixture.jsonl",
                  "tests/data/v1_segments")
# registry policies whose fresh probe traces the model checker re-verifies
# every sentinel run (flat v2, hierarchical v3, obs-profiled v4 headers —
# one per schema generation still being written)
MODEL_POLICIES = ("replay_baseline", "topology_two_level",
                  "topology_pods_adaptive")


def _model_findings() -> list[Finding]:
    """The ``model`` sentinel section: run ``repro.check``'s trace model
    checker over every committed trace fixture plus a fresh probe trace
    per schema-spanning registry policy.  Baseline is implicit and
    constant — zero violations — so any structurally illegal schedule is a
    regression (the second gate on ROADMAP item 2's hot-path rewrite,
    independent of stats equality)."""
    from repro.check import check_path, check_trace
    from repro.spec import registry
    from repro.spec.validate import probe_trace

    findings: list[Finding] = []

    def judge(label: str, result) -> None:
        n = float(len(result.violations))
        findings.append(Finding("model", f"{label}.violations", 0.0, n,
                                "equal", "ok" if result.ok else "regression"))
        for v in result.violations:
            print(f"# sentinel model: {v}", file=sys.stderr)

    for path in MODEL_FIXTURES:
        if not os.path.exists(path):
            continue
        judge(f"fixture.{os.path.basename(path)}", check_path(path))
    names = [n for n in MODEL_POLICIES if n in registry.policy_names()]
    for name in names:
        spec = registry.named(name)
        judge(f"policy.{name}", check_trace(probe_trace(spec), path=name))
    return findings


def main(argv: list[str] | None = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    full = "--full" in argv
    only = None
    if "--only" in argv:
        only = set(argv[argv.index("--only") + 1].split(","))
        known = set(BASELINES) | {"model"}
        unknown = only - known
        if unknown:
            raise SystemExit(f"--only: unknown bench(es) {sorted(unknown)}; "
                             f"choose from {sorted(known)}")

    all_findings: dict[str, list[Finding]] = {}
    skipped: dict[str, str] = {}
    with tempfile.TemporaryDirectory(prefix="sentinel-") as tmp:
        for bench, baseline_path in BASELINES.items():
            if only is not None and bench not in only:
                continue
            if not os.path.exists(baseline_path):
                skipped[bench] = f"no committed baseline {baseline_path}"
                continue
            with open(baseline_path, "r", encoding="utf-8") as fh:
                base = json.load(fh)
            out = os.path.join(tmp, f"{bench}.json")
            print(f"# sentinel: re-running {bench} at baseline parameters "
                  f"({baseline_path})", flush=True)
            if bench == "overhead":
                _overhead_rows(base, out, full)
            else:
                RUNNERS[bench](base, out)
            with open(out, "r", encoding="utf-8") as fh:
                fresh = json.load(fh)
            if bench == "overhead":
                base, fresh = _intersect_overhead(base, fresh)
            all_findings[bench] = compare(base, fresh, bench)

    if only is None or "model" in only:
        print("# sentinel: model-checking committed fixtures + fresh "
              "policy probe traces", flush=True)
        all_findings["model"] = _model_findings()

    report = render_report(all_findings, skipped)
    with open(REPORT_PATH, "w", encoding="utf-8") as fh:
        fh.write(report)
    entry = append_trajectory(all_findings)
    print(report)
    print(f"# report: {REPORT_PATH}; trajectory: {TRAJECTORY_PATH} "
          f"({len(entry['benches'])} bench(es), ok={entry['ok']})")
    return 0 if entry["ok"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
