"""Scheduler self-overhead: ns per scheduling decision at production scale.

    PYTHONPATH=src python -m benchmarks.scheduler_overhead [--fast]

ROADMAP item 2 asks what the *scheduler itself* costs as task and domain
count scale toward production (10⁵–10⁶ tasks; cf. Wang et al. on
fine-grained parallelism overheads).  This benchmark answers with the
``repro.obs`` self-profiling hooks — ``Executor(profiler=...)`` wraps the
four hot decision sites in ``perf_counter_ns`` timers:

  submit_route   choosing a queue per routed submission
  steal_scan     one dequeue attempt (local check + governed victim scan)
  batch_grab     draining batch-mates from the chosen queue
  event_append   appending one event to the ring-buffer log

and with an obs-on vs obs-off A/B: the same workload driven under
``ObsSpec(enabled=True)`` (a live ``Observation`` attached, **no**
profiler) and under ``ObsSpec()`` — observation is passive, so the wall
time delta must stay inside noise.  Gates (skipped under ``gates=False``):

  * obs-on and obs-off runs produce bit-identical ``RuntimeStats``
    (the obs layer's load-bearing invariant, asserted per configuration);
  * obs-on throughput within ``OVERHEAD_GATE`` (5%) of obs-off
    (min-of-``repeats`` wall time on both arms, the cyclic GC paused
    during timed regions, so collector pauses and scheduler jitter do
    not fail the gate).

The wall-time gate binds at *every* scale.  A single short run is noisier
than the few-percent delta the gate watches, so short configurations
don't get exempted — they get more repeats: rounds continue until each
arm has accumulated at least ``REPEAT_WALL_FLOOR_S`` of measured wall
time (capped at ``MAX_REPEATS``).  Each round runs both arms
back-to-back, with the arm *order alternating* round to round: a fixed
off-then-on order lets slow machine drift (thermal, allocator state)
masquerade as a one-sided obs cost — measured on a shared box, a fixed
order read a reproducible +11% on two provably identical arms (cProfile:
same call counts to the function), while off-vs-off read 0%.  The gated
``overhead_frac`` is the **median of per-round on/off ratios**: pairing
cancels drift, alternation cancels order bias, and the median shrugs off
background spikes landing in either arm of any single round (per-round
ratios jitter ±20% where the median holds within ±3%).  The JSON records
``repeats_used`` and the ``estimator`` name per row; ``wall_off_s``/
``wall_on_s``/``tasks_per_s`` always report the min-of-N floors.

The profiled arm is reported but not gated: the timers themselves cost a
few hundred ns per decision and that cost is exactly what this benchmark
exists to measure, not to hide.

The driven workload is synthetic and arrival-paced (``num_domains`` tasks
per scheduling round, 20% of them homed hot on domain 0 so the steal scan
has real work), under a fixed batch-4 grab so all four hot paths fire.

CSV: n_tasks,num_domains,submit_route_ns,steal_scan_ns,batch_grab_ns,
event_append_ns,wall_off_s,wall_on_s,overhead_frac,tasks_per_s

``main(json_path=...)`` (default ``BENCH_overhead.json`` when run as a
script) writes the machine-readable summary: per configuration, ns/decision
and call counts for every hot path plus the obs-on/off wall-time delta.
``--fast`` runs a reduced ladder for CI (the committed artifact comes from
the full run).
"""
from __future__ import annotations

import gc
import json
import statistics
import sys
import time
import warnings

TASK_SCALES = (1_000, 10_000, 100_000, 1_000_000)
DOMAIN_SCALES = (4, 16)
FAST_TASK_SCALES = (1_000, 20_000)
FAST_DOMAIN_SCALES = (4,)
OVERHEAD_GATE = 0.05           # obs-on may cost at most 5% throughput
REPEAT_WALL_FLOOR_S = 1.0      # accumulated per-arm wall before gating
MAX_REPEATS = 256              # adaptive-repeat ceiling per arm
MILLION_REPEATS = 2            # repeat floor for the 10^6-task rows
BATCH_SIZE = 4                 # fixed batch so batch_grab fires
STEAL_PENALTY = 4.0
HOT_EVERY = 5                  # every 5th task homed on domain 0


def _spec(num_domains: int, *, obs_enabled: bool, profile: bool):
    from repro import spec

    return spec.RuntimeSpec(
        num_domains=num_domains,
        steal_order="cyclic",
        penalty=spec.PenaltySpec(kind="constant", value=STEAL_PENALTY),
        batch=spec.BatchSpec(kind="fixed", size=BATCH_SIZE),
        obs=spec.ObsSpec(enabled=obs_enabled, profile=profile),
    )


def _drive(ex, n_tasks: int, num_domains: int) -> float:
    """Submit ``num_domains`` tasks per scheduling round (20% homed hot on
    domain 0), step between waves, drain; returns elapsed wall seconds.
    Takes a bare ``Executor`` (spec callers pass ``built.executor``).  The
    big scales overflow the event ring buffer by design — the one-shot
    warning is expected and muted here (storm analysis is not run)."""
    # GC hygiene: a collection pause landing inside one arm but not the
    # other would swamp the few-percent delta the gate watches.  The driven
    # structures are cycle-free (refcounting reclaims them), so the cyclic
    # collector is paused for the timed region.
    gc.collect()
    gc.disable()
    try:
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            t0 = time.perf_counter()
            for i in range(n_tasks):
                home = 0 if i % HOT_EVERY == 0 else i % num_domains
                ex.submit(ex.make_task(home=home))
                if i % num_domains == num_domains - 1:
                    ex.step()
            ex.run_until_drained()
            return time.perf_counter() - t0
    finally:
        gc.enable()


def measure(n_tasks: int, num_domains: int,
            repeats: int = 5) -> dict:
    """One configuration: profiled ns/decision + obs-on/off wall A/B.

    ``repeats`` is the floor; short configurations repeat adaptively
    until each arm accumulates ``REPEAT_WALL_FLOOR_S`` of wall time
    (capped at ``MAX_REPEATS``) so every row participates in the overhead
    gate.  The gated fraction is the median of per-round paired ratios
    under alternating arm order (see module doc); the reported
    ``wall_*``/``tasks_per_s`` stay min-of-N floors.
    """
    # profiled arm: ns/decision per hot path (one run; the counters are
    # totals over millions of calls, repeat noise is already averaged out)
    built_prof = _spec(num_domains, obs_enabled=True, profile=True).build()
    _drive(built_prof.executor, n_tasks, num_domains)
    prof = built_prof.obs.profiler.snapshot()
    stats_prof = built_prof.executor.metrics.snapshot()

    wall_off = wall_on = float("inf")
    acc_off = acc_on = 0.0
    ratios = []
    stats = {True: None, False: None}
    repeats_used = 0
    while repeats_used < repeats or (
            min(acc_off, acc_on) < REPEAT_WALL_FLOOR_S
            and repeats_used < MAX_REPEATS):
        # alternate which arm runs first (round parity — deterministic)
        arms = (False, True) if repeats_used % 2 == 0 else (True, False)
        walls = {}
        for on in arms:
            built = _spec(num_domains, obs_enabled=on, profile=False).build()
            walls[on] = _drive(built.executor, n_tasks, num_domains)
            stats[on] = built.executor.metrics.snapshot()
        wall_off = min(wall_off, walls[False])
        wall_on = min(wall_on, walls[True])
        acc_off += walls[False]
        acc_on += walls[True]
        ratios.append(walls[True] / walls[False])
        repeats_used += 1

    stats_off, stats_on = stats[False], stats[True]
    if stats_on != stats_off or stats_prof != stats_off:
        raise SystemExit(
            f"obs perturbed the schedule at n_tasks={n_tasks}, "
            f"num_domains={num_domains}: off={stats_off} on={stats_on} "
            f"profiled={stats_prof}")
    return {
        "n_tasks": n_tasks,
        "num_domains": num_domains,
        "ns_per_decision": prof["ns_per_call"],
        "calls": prof["calls"],
        "profile_total_ns": sum(prof["ns"].values()),
        "wall_off_s": wall_off,
        "wall_on_s": wall_on,
        "overhead_frac": statistics.median(ratios) - 1.0,
        "tasks_per_s": n_tasks / wall_off,
        "stats_identical": True,
        "repeats_used": repeats_used,
        "estimator": "paired_median",
        "gated": True,
    }


def main(task_scales=TASK_SCALES, domain_scales=DOMAIN_SCALES,
         repeats: int = 5, json_path: str | None = None,
         gates: bool = True) -> list[str]:
    lines = ["n_tasks,num_domains,submit_route_ns,steal_scan_ns,"
             "batch_grab_ns,event_append_ns,wall_off_s,wall_on_s,"
             "overhead_frac,tasks_per_s"]
    rows = []
    failures = []
    for num_domains in domain_scales:
        for n_tasks in task_scales:
            # the 10^6-task rows run multi-second walls per arm; the
            # min-of-N estimator is already tight there, so cap repeats
            row = measure(n_tasks, num_domains,
                          repeats=(min(repeats, MILLION_REPEATS)
                                   if n_tasks >= 1_000_000 else repeats))
            rows.append(row)
            ns = row["ns_per_decision"]
            lines.append(
                f"{n_tasks},{num_domains},{ns['submit_route']:.0f},"
                f"{ns['steal_scan']:.0f},{ns['batch_grab']:.0f},"
                f"{ns['event_append']:.0f},{row['wall_off_s']:.3f},"
                f"{row['wall_on_s']:.3f},{row['overhead_frac']:+.3f},"
                f"{row['tasks_per_s']:.0f}")
            if gates and row["overhead_frac"] >= OVERHEAD_GATE:
                failures.append(
                    f"n_tasks={n_tasks} num_domains={num_domains}: obs-on "
                    f"cost {row['overhead_frac']:+.1%} wall time "
                    f"(gate < {OVERHEAD_GATE:.0%})")
    if json_path:
        with open(json_path, "w", encoding="utf-8") as fh:
            json.dump({"bench": "scheduler_overhead",
                       "overhead_gate": OVERHEAD_GATE,
                       "batch_size": BATCH_SIZE, "repeats": repeats,
                       "results": rows},
                      fh, indent=2)
            fh.write("\n")
    if failures:
        raise SystemExit("scheduler_overhead gate failure:\n  "
                         + "\n  ".join(failures))
    return lines


if __name__ == "__main__":
    fast = "--fast" in sys.argv
    out = main(task_scales=FAST_TASK_SCALES if fast else TASK_SCALES,
               domain_scales=FAST_DOMAIN_SCALES if fast else DOMAIN_SCALES,
               json_path="BENCH_overhead.json")
    for ln in out:
        print(ln)
    print(f"\n# scheduler_overhead complete (BENCH_overhead.json written"
          f"{', fast ladder' if fast else ''})")
