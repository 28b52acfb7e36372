"""Reduce a JAX profiler trace (``.xplane.pb``) to intervals and totals.

What it reads:

* device planes (``/device:TPU:<n>``): the ``XLA Ops`` line, one event
  per operation executed, and the ``XLA Modules`` line, one event per
  compiled program executed (named after the jitted function);
* host planes: the harness's ``perfbench.<phase>`` annotations.

Device and host events of one trace share one clock, in nanoseconds.
Everything here is plain interval arithmetic over those events, so it
can be tested on a recorded trace without a chip.
"""

from __future__ import annotations

import bisect
import collections
import re

from perfbench.harness import ANNOTATION_PREFIX

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")


def merge(intervals) -> list:
    """Union of (start, end) intervals, sorted and disjoint."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def intersect(a, b) -> list:
    """Intersection of two merged interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


class Trace:
    """One trace, reduced to named intervals (all times in ns)."""

    def __init__(self, device_ops: dict, modules: dict, phases: dict):
        self.device_ops = device_ops    # device -> [(name, start, end)]
        self.modules = modules          # device -> [(name, start, end)]
        self.phases = {k: merge(v) for k, v in phases.items()}
        self._starts = collections.defaultdict(list, {
            k: [s for s, _ in v] for k, v in self.phases.items()})
        self._busy = {d: merge((s, e) for _, s, e in ops)
                      for d, ops in device_ops.items()}

    @classmethod
    def from_file(cls, path: str) -> "Trace":
        import jax
        data = jax.profiler.ProfileData.from_file(path)
        device_ops, modules = {}, {}
        phases = collections.defaultdict(list)
        for plane in data.planes:
            if DEVICE_PLANE.match(plane.name):
                for line in plane.lines:
                    if line.name == OPS_LINE:
                        device_ops[plane.name] = [
                            (ev.name, ev.start_ns, ev.end_ns)
                            for ev in line.events]
                    elif line.name == MODULES_LINE:
                        modules[plane.name] = [
                            (ev.name, ev.start_ns, ev.end_ns)
                            for ev in line.events]
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for ev in line.events:
                        if ev.name.startswith(ANNOTATION_PREFIX):
                            phases[ev.name[len(ANNOTATION_PREFIX):]].append(
                                (ev.start_ns, ev.end_ns))
        return cls(device_ops, modules, dict(phases))

    @property
    def devices(self) -> list:
        return sorted(self.device_ops)

    def wall_ns(self, phase: str) -> float:
        return total(self.phases.get(phase, []))

    def busy_ns(self, phase: str) -> float:
        """Device busy time inside ``phase``, averaged over devices."""
        span = self.phases.get(phase, [])
        if not self.devices:
            return 0.0
        return sum(total(intersect(self._busy[d], span))
                   for d in self.devices) / len(self.devices)

    def idle_share(self, phase: str):
        """1 - busy/wall of ``phase``; None when the phase is absent."""
        wall = self.wall_ns(phase)
        if not wall or not self.devices:
            return None
        return 1.0 - self.busy_ns(phase) / wall

    def module_ns(self, phase: str, pattern: str,
                  invert: bool = False) -> float:
        """Device time of programs whose name matches ``pattern``
        (``re.search``; with ``invert``, of all the others), clipped to
        ``phase``, summed over devices."""
        rx = re.compile(pattern)
        span = self.phases.get(phase, [])
        out = 0.0
        for mods in self.modules.values():
            hits = merge((s, e) for n, s, e in mods
                         if bool(rx.search(n)) != invert)
            out += total(intersect(hits, span))
        return out

    def _clip(self, phase: str, s: float, e: float) -> float:
        """Length of (s, e) inside ``phase``."""
        span = self.phases.get(phase, [])
        i = max(0, bisect.bisect_right(self._starts[phase], s) - 1)
        out = 0.0
        while i < len(span) and span[i][0] < e:
            out += max(0.0, min(e, span[i][1]) - max(s, span[i][0]))
            i += 1
        return out

    def top_ops(self, phase: str, k: int = 10) -> list:
        """The ``k`` device operations with the most time in ``phase``,
        as [name, seconds summed over devices]."""
        acc = collections.Counter()
        for ops in self.device_ops.values():
            for name, s, e in ops:
                acc[name] += self._clip(phase, s, e)
        return [[n, t / 1e9] for n, t in acc.most_common(k) if t > 0]

    def idle_gaps(self, phase: str, labels: list, k: int = 10) -> list:
        """The ``k`` longest device idle gaps inside ``phase``, each
        labelled by the first of ``labels`` (phases) the host was in at
        the gap's midpoint, as [label, seconds]."""
        span = self.phases.get(phase, [])
        gaps = []
        for d in self.devices:
            busy = intersect(self._busy[d], span)
            j = 0
            for ws, we in span:
                cursor = ws
                while j < len(busy) and busy[j][0] < we:
                    if busy[j][0] > cursor:
                        gaps.append((busy[j][0] - cursor, cursor))
                    cursor = max(cursor, busy[j][1])
                    j += 1
                if we > cursor:
                    gaps.append((we - cursor, cursor))
        out = []
        for dur, s in sorted(gaps, reverse=True)[:k]:
            mid = s + dur / 2
            label = next((p for p in labels
                          if self._clip(p, mid, mid + 1) > 0), "other")
            out.append([label, dur / 1e9])
        return out
