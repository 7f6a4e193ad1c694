"""The benchmark's harness: everything that is not one cell's own.

``BENCHMARK.json`` names every cell, configuration, traffic mix and
metric; this module finds the file of each by that name:

    bench/configs/<config>.json      sizes, source, limits of a configuration
    bench/traffic/<traffic>.json     the traffic mix; ``"driver"`` names
    bench/drivers/<driver>.py        the execution path it drives
    bench/metrics/<metric>.py        one reader per per-layer metric

so a later cell, configuration, traffic mix or metric is new files plus
manifest entries, and no existing file changes.

A driver module has ``setup(ctx) -> cell``; the cell has
``window(seconds) -> Window``, ``release()`` and ``check() -> {name:
(value, limit)}``.  A metric module has ``read(ctx, win, trace)``, which
returns a number or ``None`` when it finds nothing to read.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import math
import sys
import time
from pathlib import Path
from typing import Any

ROOT = Path(__file__).resolve().parents[1]


def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    """Import a file by path; its module name is its path under the root."""
    if not path.is_file():
        raise FileNotFoundError(f"no file {path}")
    name = "bench_file." + str(path.with_suffix("")).replace("/", ".")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One workload of the manifest with the files it names, loaded."""
    root: Path
    manifest: dict
    workload: dict
    config: dict
    traffic: dict

    @property
    def name(self) -> str:
        return self.workload["name"]

    @property
    def chips(self) -> int:
        return int(self.workload["chips"])

    def metrics(self, section: str) -> list[dict]:
        """The manifest's metrics of ``section`` that this cell reports."""
        return [m for m in self.manifest[section]
                if self.name in m.get("workloads", [self.name])]

    def driver(self):
        return load_module(self.root / "bench" / "drivers"
                           / f"{self.traffic['driver']}.py")

    def reader(self, metric: str):
        return load_module(self.root / "bench" / "metrics" / f"{metric}.py")


def find_cell(root: Path, workload: str) -> Cell:
    manifest = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[workload]
    configs = {c["name"]: c for c in manifest["configs"]}
    config = load_json(root / configs[w["config"]]["file"])
    traffic = load_json(root / "bench" / "traffic" / f"{w['traffic']}.json")
    return Cell(root, manifest, w, config, traffic)


class Spans:
    """Host spans around calls into the program's layers.

    Each span is kept as ``(start, end)`` on ``time.perf_counter``; in a
    traced run it is also a ``TraceAnnotation``, so idle gaps on the
    device can be labelled by what the host was doing.
    """

    def __init__(self, annotate: bool = False):
        self.annotate = annotate
        self.records: dict[str, list[tuple[float, float]]] = {}

    @contextlib.contextmanager
    def __call__(self, name: str):
        if self.annotate:
            import jax
            ctx = jax.profiler.TraceAnnotation(name)
        else:
            ctx = contextlib.nullcontext()
        t0 = time.perf_counter()
        with ctx:
            yield
        self.records.setdefault(name, []).append((t0, time.perf_counter()))

    def durations(self, name: str) -> list[float]:
        return [b - a for a, b in self.records.get(name, [])]


@dataclasses.dataclass
class Window:
    """What one measured window produced."""
    seconds: float                       # the window's length, host clock
    attempted: int
    failed: int
    metrics: dict[str, float]            # end-to-end metrics by name
    facts: dict[str, Any] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Context:
    """What a driver and a metric reader are given."""
    cell: Cell
    seed: int
    spans: Spans
    peaks: dict
    interpret: bool                      # Pallas in interpret mode (CPU)


def peaks_for(root: Path, device_kind: str) -> dict:
    table = load_json(root / "bench" / "peaks.json")
    if device_kind not in table["devices"]:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"bench/peaks.json (have {sorted(table['devices'])})")
    return table["devices"][device_kind]


def prng_key(seed: int):
    """A JAX key from any whole number: ``jax.random.key`` wraps seeds
    above 32 bits to the same key, so the high bits are folded in."""
    import jax
    if seed < 0:
        raise ValueError(f"seed {seed} is negative")
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def percentile(values, q: float) -> float:
    """Exact nearest-rank percentile (``q`` in [0, 100]).

    Copied from the program's ``obs.metrics.percentile`` so that the
    yardstick cannot move with the program.
    """
    if not values:
        raise ValueError("percentile of an empty sample")
    ordered = sorted(values)
    if q == 0.0:
        return ordered[0]
    return ordered[math.ceil(q / 100.0 * len(ordered)) - 1]


CACHE_OPTIONS = ("jax_compilation_cache_dir",
                 "jax_persistent_cache_min_compile_time_secs",
                 "jax_persistent_cache_min_entry_size_bytes")


def use_compile_cache(root: Path) -> str:
    """JAX's persistent cache at a fixed path inside the checkout, for
    every program however short its compile."""
    import jax
    path = str(root / ".bench_cache" / "jax")
    for option, value in zip(CACHE_OPTIONS, (path, 0.0, 0)):
        jax.config.update(option, value)
    return path


class CompileCounter:
    """Counts compilations and persistent-cache loads while ``armed``."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_hits")

    def __init__(self):
        import jax
        self.armed = False
        self.count = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event: str, **_):
        if self.armed and event in self.EVENTS:
            self.count += 1

    def _duration(self, event: str, _secs: float, **_):
        self._event(event)


def memory_peak_bytes(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats()
        if stats and "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks, default=0)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)
