"""Plain references for the runtime's locality queue and event log.

``ReferenceQueues`` is ``repro.runtime.DomainQueues`` written as
O(domains) linear scans, with no bitmask, heap or counter: per-domain FIFO
queues, local pop first, and a steal scan over foreign queues, flat or
nearest-first by topology tier.  ``ReferenceEventLog`` is the event log as
one frozen ``Event`` per emit in a ``deque``.  The runtime tests hold the
runtime's classes to these: same victims, same RNG draws, same events,
counts and CSV export.  ``reference_executor`` builds an ``Executor`` and
swaps both in.
"""
from __future__ import annotations

import warnings
from collections import Counter, deque
from typing import Any, Optional

from repro.runtime import Event, Executor, Popped


class ReferenceQueues:
    """Per-domain FIFO queues with linear-scan victim selection."""

    def __init__(self, num_domains: int, steal_order: str = "cyclic",
                 rng=None, topology=None):
        self.num_domains = num_domains
        self.steal_order = steal_order
        self.topology = topology
        self._rng = rng
        # each slot is (item, enqueue-time cost), as in DomainQueues
        self._queues = [deque() for _ in range(num_domains)]
        self._costs = [0.0] * num_domains

    def enqueue(self, item: Any, domain: int) -> None:
        cost = float(getattr(item, "cost", 1.0))
        self._queues[domain].append((item, cost))
        self._costs[domain] += cost

    def _pop(self, domain: int) -> Any:
        item, cost = self._queues[domain].popleft()
        self._costs[domain] -= cost
        return item

    def dequeue(self, domain: int, allow_steal: bool = True,
                min_victim=1) -> Optional[Popped]:
        if self._queues[domain]:
            return Popped(self._pop(domain), domain, False, 0, 0.0)
        if not allow_steal:
            return None
        topo = self.topology
        if topo is not None and topo.hierarchical:
            victim = self._nearest(domain, min_victim)
        else:
            mv = self._level_min(min_victim, 1)
            victim = (None if mv is None
                      else self._scan(domain, self._foreign(domain),
                                      max(mv, 1)))
        if victim is None:
            return None
        if topo is None:
            level, dist = 1, 1.0
        else:
            level, dist = topo.level(domain, victim), topo.distance(domain,
                                                                    victim)
        return Popped(self._pop(victim), victim, True, level, dist)

    def drain(self, domain: int, n: int, budget: Optional[float] = None,
              spent: float = 0.0) -> list[Any]:
        out = []
        q = self._queues[domain]
        while n > 0 and q:
            if budget is not None:
                if spent + q[0][1] > budget:
                    break
                spent += q[0][1]
            out.append(self._pop(domain))
            n -= 1
        return out

    @staticmethod
    def _level_min(min_victim, level: int) -> Optional[int]:
        if min_victim is None or isinstance(min_victim, int):
            return min_victim
        if not len(min_victim):
            return None
        return min_victim[min(level - 1, len(min_victim) - 1)]

    def _foreign(self, domain: int) -> list[int]:
        """Every other domain, in the cyclic visit order (domain+1 first)."""
        n = self.num_domains
        return [(domain + off) % n for off in range(1, n)]

    def _nearest(self, domain: int, min_victim) -> Optional[int]:
        topo = self.topology
        for level in range(1, topo.num_levels + 1):
            mv = self._level_min(min_victim, level)
            if mv is None:
                continue
            tier = [d for d in self._foreign(domain)
                    if topo.level(domain, d) == level]
            victim = self._scan(domain, tier, max(mv, 1))
            if victim is not None:
                return victim
        return None

    def _scan(self, domain: int, visit: list[int], mv: int) -> Optional[int]:
        """The configured steal order over ``visit`` (cyclic order)."""
        if self.steal_order == "cyclic":
            for d in visit:
                if len(self._queues[d]) >= mv:
                    return d
            return None
        eligible = sorted(d for d in visit if len(self._queues[d]) >= mv)
        if not eligible:
            return None
        if self.steal_order == "longest":
            return max(eligible, key=lambda d: (len(self._queues[d]), -d))
        if self.steal_order == "cost_weighted":
            return max(eligible, key=lambda d: (self._costs[d], -d))
        return int(eligible[int(self._rng.integers(len(eligible)))])

    def __len__(self) -> int:
        return sum(len(q) for q in self._queues)

    def queue_sizes(self) -> list[int]:
        return [len(q) for q in self._queues]

    def depth(self, domain: int) -> int:
        return len(self._queues[domain])

    def cost(self, domain: int) -> float:
        return self._costs[domain]

    def queue_costs(self) -> list[float]:
        return list(self._costs)


class ReferenceEventLog:
    """Bounded ring of events, one frozen ``Event`` per emit."""

    def __init__(self, maxlen: int = 65536):
        if maxlen is None or maxlen < 1:
            raise ValueError(f"event log maxlen must be >= 1, got {maxlen!r}")
        self.maxlen = int(maxlen)
        self._buf: deque[Event] = deque(maxlen=self.maxlen)
        self._counts: Counter[str] = Counter()
        self._warned_overflow = False

    def emit(self, step: int, kind: str, worker: int, domain: int,
             task_uid: int, src_domain: int = -1, cost: float = 0.0,
             penalty: float = 0.0) -> None:
        if not self._warned_overflow and len(self._buf) == self.maxlen:
            self._warned_overflow = True
            warnings.warn(f"event log overflow: ring of {self.maxlen} "
                          "drops its oldest events", RuntimeWarning,
                          stacklevel=2)
        self._buf.append(Event(step, kind, worker, domain, task_uid,
                               src_domain, cost, penalty))
        self._counts[kind] += 1

    def counts(self) -> dict[str, int]:
        return dict(self._counts)

    @property
    def total(self) -> int:
        return sum(self._counts.values())

    @property
    def dropped(self) -> int:
        return self.total - len(self._buf)

    def tail(self, n: int = 50) -> list[Event]:
        return list(self._buf)[-n:]

    def __len__(self) -> int:
        return len(self._buf)

    def __iter__(self):
        return iter(self._buf)

    def to_csv_lines(self) -> list[str]:
        out = [f"# events total={self.total} retained={len(self._buf)} "
               f"dropped={self.dropped} window={self.maxlen}",
               "step,kind,worker,domain,task_uid,src_domain,cost,penalty"]
        out += [f"{e.step},{e.kind},{e.worker},{e.domain},{e.task_uid},"
                f"{e.src_domain},{e.cost:g},{e.penalty:g}" for e in self._buf]
        return out


def reference_executor(*args, **kwargs) -> Executor:
    """An ``Executor`` running on ``ReferenceQueues`` and, when it records
    events, ``ReferenceEventLog``: the same arguments give the same run."""
    ex = Executor(*args, **kwargs)
    ex.queues = ReferenceQueues(ex.num_domains, ex.queues.steal_order,
                                rng=ex.rng, topology=ex.topology)
    ex._dequeue = ex.queues.dequeue
    if ex.events is not None:
        ex.events = ReferenceEventLog(ex.events.maxlen)
    return ex
