"""The program's own side of a traced window: the ``repro.*`` host spans
the engines write (`repro.core.telemetry.span`), the program dispatches
the profiler writes for every call into a jitted function
(``PjitFunction(<function>)``), and its device-to-host reads
(``np.asarray(jax.Array)``), read from the same ``.xplane.pb`` as
`chipbench.trace.Trace`, on the same clock.

The device side, the window and the stopped spans (``chipbench.audit``)
are the `Trace`'s.  A span or dispatch counts when it starts inside the
window and outside the stopped spans.  A dispatch writes two nested
``PjitFunction`` events on one thread; it is counted once, by the outer
one.  An idle gap is an interval of the window, outside the stopped spans,
in which no program runs on device 0 (the union of its ``XLA Modules``
runs, so that the gaps between operations inside a program are not charged
to the host); it counts for a span when that span is open at the gap's
midpoint.  Host and device clocks are aligned to within about a
millisecond, so a single gap charged to a span much shorter than that may
belong to its neighbour.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import weakref
from typing import Dict, Iterable, List, Optional, Tuple

from chipbench.trace import Event, Trace, _clip, _subtract, _union

ENGINE = ("repro.engine.step", "repro.engine.reset", "repro.engine.prefill")
SNAPSHOT = ("repro.snapshot.pin", "repro.snapshot.unpin",
            "repro.snapshot.read")
DISPATCH = "PjitFunction("
SYNC_EVENT = "np.asarray(jax.Array)"

_LOADED: "weakref.WeakKeyDictionary[Trace, ProgramTrace]" = \
    weakref.WeakKeyDictionary()


def load(run, trace: Trace) -> Optional["ProgramTrace"]:
    """The program side of ``trace``, read once from the run's trace files;
    None when the run kept none."""
    if trace not in _LOADED:
        if not run.trace_dir:
            return None
        ProgramTrace.from_dir(run.trace_dir, trace)
    return _LOADED[trace]


def steps(run) -> Optional[int]:
    """Steps of the window: decode steps of a served model, else the
    driver's step count."""
    ctx = run.obs.get("decode_ctx")
    return len(ctx) if ctx else run.obs.get("steps")


def _outermost(events: List[Event]) -> List[Event]:
    """The events of one thread that lie inside no other."""
    out: List[Event] = []
    end = None
    for e in sorted(events, key=lambda e: (e.start, -e.end)):
        if end is None or e.start >= end:
            out.append(e)
            end = e.end
    return out


class ProgramTrace:
    """The program's spans, dispatches and reads in one traced window.
    Building one makes it the program side of ``trace`` for `load`."""

    def __init__(self, trace: Trace, spans: List[Event],
                 dispatches: List[Event], reads: List[Event]):
        self.trace = trace
        keep = trace._in_window
        self.spans = keep(sorted(spans, key=lambda e: (e.start, -e.end)))
        self.dispatches = keep(sorted(dispatches, key=lambda e: e.start))
        self.reads = keep(sorted(reads, key=lambda e: e.start))
        _LOADED[trace] = self

    # -- loading -----------------------------------------------------------
    @classmethod
    def from_dir(cls, path: str, trace: Trace) -> "ProgramTrace":
        files = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                 recursive=True))
        if not files:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        from jax.profiler import ProfileData
        return cls.from_profile(ProfileData.from_file(files[-1]), trace)

    @classmethod
    def from_profile(cls, pdata, trace: Trace) -> "ProgramTrace":
        spans: List[Event] = []
        dispatches: List[Event] = []
        reads: List[Event] = []
        for plane in pdata.planes:
            if not plane.name.startswith("/host"):
                continue
            for line in plane.lines:
                mine: List[Event] = []
                for e in line.events:
                    name = e.name
                    if name.startswith(("repro.", "chipbench.")):
                        spans.append(Event(name, e.start_ns, e.end_ns))
                    elif name.startswith(DISPATCH):
                        mine.append(Event(name[len(DISPATCH):-1],
                                          e.start_ns, e.end_ns))
                    elif name == SYNC_EVENT:
                        reads.append(Event(name, e.start_ns, e.end_ns))
                dispatches.extend(_outermost(mine))
        return cls(trace, spans, dispatches, reads)

    # -- reductions ----------------------------------------------------------
    def named(self, names: Iterable[str]) -> List[Event]:
        names = set(names)
        return [e for e in self.spans if e.name in names]

    def count(self, name: str) -> int:
        return len(self.named([name]))

    def inside(self, events: List[Event], names: Iterable[str]
               ) -> List[Event]:
        """The events that start while a span of ``names`` is open."""
        spans = _union([(h.start, h.end) for h in self.named(names)])
        starts = [s for s, _ in spans]
        out = []
        for e in events:
            i = bisect.bisect_right(starts, e.start) - 1
            if i >= 0 and e.start < spans[i][1]:
                out.append(e)
        return out

    def idle_gaps(self) -> List[Tuple[int, int]]:
        """Intervals of the window in which no program runs on device 0,
        less the stopped spans."""
        t = self.trace
        runs = _union(_clip([(e.start, e.end)
                             for e in t.modules.get(t.devices[0], [])],
                            t.w0, t.w1))
        gaps, prev = [], t.w0
        for s, e in runs + [(t.w1, t.w1)]:
            if s > prev:
                gaps.append((prev, s))
            prev = max(prev, e)
        return _subtract(gaps, t.stopped)

    def idle_ns(self, names: Iterable[str]) -> int:
        """Device idle time in gaps at whose midpoint a span of ``names``
        is open."""
        spans = _union([(h.start, h.end) for h in self.named(names)])
        starts = [s for s, _ in spans]
        total = 0
        for s, e in self.idle_gaps():
            mid = (s + e) // 2
            i = bisect.bisect_right(starts, mid) - 1
            if i >= 0 and mid < spans[i][1]:
                total += e - s
        return total

    def idle_by_span(self) -> Dict[str, float]:
        """Idle seconds of device 0 by the innermost span open at each
        gap's midpoint: a ``repro.*`` span, else the benchmark's own
        (``chipbench.window`` where no other is open)."""
        spans = [h for h in self.spans if h.name != "chipbench.window"]
        starts = [h.start for h in spans]
        cover = _union([(h.start, h.end) for h in spans])
        cstarts = [s for s, _ in cover]
        out: Dict[str, int] = collections.Counter()
        for s, e in self.idle_gaps():
            mid = (s + e) // 2
            name = "chipbench.window"
            c = bisect.bisect_right(cstarts, mid) - 1
            if c >= 0 and mid < cover[c][1]:
                # the shortest open span, among those that start in the
                # covering interval, as `Trace.breakdown` picks
                lo = bisect.bisect_left(starts, cover[c][0])
                hi = bisect.bisect_right(starts, mid)
                name = min((h for h in spans[lo:hi] if mid < h.end),
                           key=lambda h: h.dur).name
            out[name] += e - s
        return {k: v / 1e9 for k, v in out.most_common()}
