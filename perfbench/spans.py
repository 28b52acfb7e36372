"""The program's own spans in a profiler trace, against the device.

The program (``src/repro/obs.py``) records ``repro.*`` spans on the host
planes of a JAX profiler trace, one host line per thread, on the clock of
the device's ``XLA Ops`` line. ``SpanTrace`` reads them beside the
:class:`perfbench.trace_reduce.Trace` of the same file (phases and device
busy time) and answers, per phase:

* self time: time host lines spend inside spans of one name prefix and
  not inside the copies and device waits (``repro.xfer.*``) under them;
* the device idle time under that self time;
* sums of a span stat (``nbytes`` of the copies) and span counts;
* each span name's share of the device idle time (:meth:`idle_by_span`).

A trace of a program that records no spans reads as empty: ``spans`` is
``[]`` and every time and sum is 0.
"""

from __future__ import annotations

import bisect
import collections

from perfbench.trace_reduce import Trace, intersect, merge, total

SPAN_PREFIX = "repro."
WAITS = (SPAN_PREFIX + "xfer.",)     # copies, and the waits for the device


def read_spans(path: str) -> list:
    """``[(host line, name, start, end, {stat: value})]`` of every
    ``repro.`` event on the host planes of a trace file."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    return [((plane.name, k), ev.name, ev.start_ns, ev.end_ns,
             dict(ev.stats))
            for plane in data.planes if plane.name.startswith("/host:")
            for k, line in enumerate(plane.lines) for ev in line.events
            if ev.name.startswith(SPAN_PREFIX)]


def subtract(a, b) -> list:
    """``a`` less ``b``, both merged interval lists."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k, cur = j, s
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def innermost(events) -> dict:
    """Nested ``(start, end, name)`` spans of one line, each instant given
    to the innermost open span: ``{name: [(start, end)]}``."""
    out = collections.defaultdict(list)
    stack, cursor = [], None
    for s, e, name in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            end, top = stack.pop()
            out[top].append((cursor, end))
            cursor = end
        if stack:
            out[stack[-1][1]].append((cursor, s))
        stack.append((e, name))
        cursor = s
    while stack:
        end, top = stack.pop()
        out[top].append((cursor, end))
        cursor = end
    return {k: [(s, e) for s, e in v if e > s] for k, v in out.items()}


class SpanTrace:
    """A trace's program spans, with its phases and device busy time
    (all times in ns)."""

    def __init__(self, trace: Trace, spans: list):
        self.trace = trace
        self.spans = list(spans)
        self._busy = {d: merge((s, e) for _, s, e in ops)
                      for d, ops in trace.device_ops.items()}
        self._busy_starts = {d: [s for s, _ in v]
                             for d, v in self._busy.items()}

    @classmethod
    def from_file(cls, path: str) -> "SpanTrace":
        return cls(Trace.from_file(path), read_spans(path))

    def _phase(self, phase: str) -> list:
        return self.trace.phases.get(phase, [])

    def _starts_in(self, phase: str, start: float) -> bool:
        return bool(intersect(self._phase(phase), [(start, start + 1)]))

    def _idle_in(self, intervals) -> float:
        """Device idle time inside merged ``intervals``, averaged over
        devices (0 without a device)."""
        if not self._busy:
            return 0.0
        idle = 0.0
        for d, busy in self._busy.items():
            starts = self._busy_starts[d]
            for a, b in intervals:
                idle += b - a
                i = max(0, bisect.bisect_right(starts, a) - 1)
                while i < len(busy) and busy[i][0] < b:
                    idle -= max(0, min(b, busy[i][1]) - max(a, busy[i][0]))
                    i += 1
        return idle / len(self._busy)

    def _self_lines(self, phase: str, prefix: str, exclude) -> list:
        """Per host line, the merged intervals inside ``phase`` in which
        the line is inside a span named ``prefix``* and not inside one
        named with any of the ``exclude`` prefixes."""
        inside = collections.defaultdict(list)
        outside = collections.defaultdict(list)
        for line, name, s, e, _ in self.spans:
            if name.startswith(prefix):
                inside[line].append((s, e))
            elif name.startswith(tuple(exclude)):
                outside[line].append((s, e))
        span = self._phase(phase)
        return [subtract(intersect(merge(ivs), span), merge(outside[line]))
                for line, ivs in inside.items()]

    def self_ns(self, phase: str, prefix: str, exclude=WAITS) -> float:
        """Time in ``phase`` that host lines spend inside spans named
        ``prefix``* and not inside spans named with an ``exclude``
        prefix, summed over lines."""
        return sum(total(ivs) for ivs in
                   self._self_lines(phase, prefix, exclude))

    def idle_under_ns(self, phase: str, prefix: str,
                      exclude=WAITS) -> float:
        """Time in ``phase`` in which the device ran nothing while some
        host line was in the self time of :meth:`self_ns` (union over
        lines), averaged over devices."""
        return self._idle_in(merge(
            iv for ivs in self._self_lines(phase, prefix, exclude)
            for iv in ivs))

    def stat_sum(self, phase: str, prefix: str, stat: str) -> float:
        """Sum of ``stat`` over the spans named ``prefix``* that start
        inside ``phase``."""
        return float(sum(st.get(stat, 0) for _, name, s, _, st in self.spans
                         if name.startswith(prefix)
                         and self._starts_in(phase, s)))

    def count(self, phase: str, prefix: str) -> int:
        """Spans named ``prefix``* that start inside ``phase``."""
        return sum(1 for _, name, s, _, _ in self.spans
                   if name.startswith(prefix) and self._starts_in(phase, s))

    def idle_by_span(self, phase: str) -> dict:
        """For each span name: its self time in ``phase`` (its spans less
        their child spans, summed over lines); the device idle time under
        that self time, merged over lines; and its share of the device
        idle time, each instant shared in equal parts among the lines then
        in a span, so that the shares of all names add up to the idle time
        some span covers: ``{name: [self_ns, idle_ns, share_ns]}``."""
        owned = collections.defaultdict(list)
        by_line = collections.defaultdict(list)
        for line, name, s, e, _ in self.spans:
            by_line[line].append((s, e, name))
        span = self._phase(phase)
        for events in by_line.values():
            for name, ivs in innermost(events).items():
                owned[name].append(intersect(merge(ivs), span))
        out = {}
        for name, per_line in owned.items():
            under = merge(iv for ivs in per_line for iv in ivs)
            out[name] = [sum(total(ivs) for ivs in per_line),
                         self._idle_in(under), 0.0]
        points = sorted((t, d, name) for name, per_line in owned.items()
                        for ivs in per_line for s, e in ivs
                        for t, d in ((s, 1), (e, -1)))
        active = collections.Counter()
        prev = None
        for t, d, name in points:
            n = sum(active.values())
            if n and t > prev:
                idle = self._idle_in([(prev, t)])
                for k, m in active.items():
                    out[k][2] += idle * m / n
            active[name] += d
            prev = t
        return out
