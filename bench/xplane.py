"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read.

``load`` parses the file with ``jax.profiler.ProfileData``; everything
else works on plain lists, so it is checked on synthetic traces.

- Device operations are the events of each TPU plane's ``XLA Ops`` line,
  programs those of its ``XLA Modules`` line.
- Host spans are the benchmark's ``TraceAnnotation`` events, named
  ``bench.*``; ``bench.window`` marks the measured window, and every
  reduction is clipped to it.
- Busy time is the union of a device's operation intervals; idle is the
  rest of the window.  Device figures are means over the chips used.
"""
from __future__ import annotations

import dataclasses
import glob
import re
from collections import defaultdict

COLLECTIVE = re.compile(r"collective-permute|all-gather|all-reduce|"
                        r"reduce-scatter|all-to-all|ppermute|"
                        r"\bsend\b|\brecv\b", re.I)
WINDOW = "bench.window"


@dataclasses.dataclass
class Event:
    name: str
    start: float            # seconds, on the trace's clock
    end: float


@dataclasses.dataclass
class Device:
    name: str
    ops: list[Event]
    modules: list[Event]


@dataclasses.dataclass
class Trace:
    devices: list[Device]
    spans: list[Event]      # host spans named bench.*

    # -- the window ------------------------------------------------------
    @property
    def window(self) -> tuple[float, float]:
        wins = [s for s in self.spans if s.name == WINDOW]
        if not wins:
            raise ValueError("the trace holds no bench.window span")
        return wins[0].start, wins[0].end

    @property
    def window_s(self) -> float:
        lo, hi = self.window
        return hi - lo

    def _clip(self, events: list[Event]) -> list[Event]:
        lo, hi = self.window
        return [Event(e.name, max(e.start, lo), min(e.end, hi))
                for e in events if e.end > lo and e.start < hi]

    # -- busy and idle -----------------------------------------------------
    def busy_intervals(self, dev: Device) -> list[tuple[float, float]]:
        return merge([(e.start, e.end) for e in self._clip(dev.ops)])

    def busy_s(self) -> float:
        """Seconds in which an operation ran, mean over devices."""
        if not self.devices:
            return 0.0
        return sum(sum(b - a for a, b in self.busy_intervals(d))
                   for d in self.devices) / len(self.devices)

    def idle_share(self) -> float | None:
        if not self.devices or self.window_s <= 0:
            return None
        return 1.0 - self.busy_s() / self.window_s

    # -- operations ----------------------------------------------------------
    def op_seconds(self, dev: Device) -> dict[str, float]:
        out: dict[str, float] = defaultdict(float)
        for e in self._clip(dev.ops):
            out[e.name] += e.end - e.start
        return dict(out)

    def top_ops(self, n: int = 10) -> list[list]:
        """The ``n`` operations with most device time, mean over devices."""
        total: dict[str, float] = defaultdict(float)
        for d in self.devices:
            for name, s in self.op_seconds(d).items():
                total[name] += s / len(self.devices)
        ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
        return [[name, s] for name, s in ranked]

    def _inside(self, events: list[Event], pattern: str) -> list[Event]:
        lo, hi = self.window
        rx = re.compile(pattern)
        return [e for e in events
                if lo <= e.start and e.end <= hi and rx.search(e.name)]

    def matching_ops(self, dev: Device, pattern: str) -> list[Event]:
        """Operations named by ``pattern`` that lie wholly in the window."""
        return self._inside(dev.ops, pattern)

    def matching_modules(self, dev: Device, pattern: str) -> list[Event]:
        """Programs named by ``pattern`` that lie wholly in the window."""
        return self._inside(dev.modules, pattern)

    def collective_share(self) -> float | None:
        """Device time in collective operations over busy time, mean over
        devices; ``None`` when no collective ran."""
        shares = []
        for d in self.devices:
            busy = sum(b - a for a, b in self.busy_intervals(d))
            coll = sum(e.end - e.start for e in self._clip(d.ops)
                       if COLLECTIVE.search(e.name))
            if busy > 0:
                shares.append(coll / busy)
        if not shares or not any(shares):
            return None
        return sum(shares) / len(shares)

    # -- idle gaps by host span ------------------------------------------------
    def idle_gaps(self, n: int = 10) -> list[list]:
        """The ``n`` longest idle gaps of the first device, each named by the
        innermost host span open at its middle (``bench.window`` when no
        other is)."""
        if not self.devices:
            return []
        lo, hi = self.window
        busy = self.busy_intervals(self.devices[0])
        gaps, t = [], lo
        for a, b in busy:
            if a > t:
                gaps.append((t, a))
            t = max(t, b)
        if hi > t:
            gaps.append((t, hi))
        gaps.sort(key=lambda g: g[0] - g[1])
        return [[self.span_at((a + b) / 2), b - a] for a, b in gaps[:n]]

    def span_at(self, t: float) -> str:
        open_ = [s for s in self.spans if s.start <= t <= s.end]
        if not open_:
            return "none"
        return min(open_, key=lambda s: s.end - s.start).name


def merge(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


HLO_OP = re.compile(r"(%\S+) = .*? ([\w-]+)\(")


def op_name(hlo: str) -> str:
    """``%name kind`` from an operation's HLO text, e.g.
    ``%jacobi_sweep_pallas.1 custom-call``; other names stay as they are."""
    m = HLO_OP.match(hlo)
    return f"{m.group(1)} {m.group(2)}" if m else hlo


def _events(line, name=lambda n: n, keep=lambda n: True) -> list[Event]:
    return [Event(name(e.name), e.start_ns * 1e-9,
                  (e.start_ns + e.duration_ns) * 1e-9)
            for e in line.events if keep(e.name)]


def parse(path: str, n_devices: int) -> Trace:
    """The first ``n_devices`` TPU planes and the host's bench spans."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, spans = [], []
    for plane in pd.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            ops = (_events(lines["XLA Ops"], op_name) if "XLA Ops" in lines
                   else [])
            mods = (_events(lines["XLA Modules"]) if "XLA Modules" in lines
                    else [])
            devices.append((int(plane.name.rsplit(":", 1)[1]),
                            Device(plane.name, ops, mods)))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                spans += _events(ln, keep=lambda n: n.startswith("bench."))
    devices.sort(key=lambda d: d[0])
    return Trace([d for _, d in devices[:n_devices]], spans)


def load(trace_dir: str, n_devices: int) -> Trace:
    paths = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return parse(paths[0], n_devices)
