"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

The trace is read with ``jax.profiler.ProfileData``.  Device planes are
``/device:TPU:<n>``; on each, the ``XLA Ops`` line holds one event per
operation run and the ``XLA Modules`` line one per program run (named
``jit_<function>(<id>)``).  Host planes hold the benchmark's own spans
(``chipbench.*``), among them ``chipbench.window`` around the measured
window: every device number is taken inside it, less the spans
``chipbench.audit`` in which the window's clock was stopped.

- busy time: the union of the operation intervals on a device, averaged
  over the devices used;
- operation and program time: summed durations, by name;
- exposed collective time: the intervals in which a collective runs and no
  other operation does, averaged over the devices;
- idle gaps: the intervals in the window with no operation on device 0,
  each charged to the innermost benchmark span open at its midpoint.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import re
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

DEVICE = re.compile(r"^/device:TPU:(\d+)$")


class Event:
    __slots__ = ("name", "start", "end")

    def __init__(self, name: str, start: int, end: int):
        self.name, self.start, self.end = name, start, end

    @property
    def dur(self) -> int:
        return self.end - self.start


def _union(intervals: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _length(intervals) -> int:
    return sum(e - s for s, e in intervals)


def _clip(intervals, lo: int, hi: int):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


class Trace:
    """The reduced trace of one traced window."""

    def __init__(self, ops: Dict[int, List[Event]],
                 modules: Dict[int, List[Event]], host: List[Event],
                 window: Optional[Tuple[int, int]] = None):
        self.ops, self.modules, self.host = ops, modules, host
        if window is None:
            wins = [e for e in host if e.name == "chipbench.window"]
            if wins:
                window = (wins[0].start, wins[0].end)
            else:
                evs = [e for d in ops.values() for e in d]
                window = (min(e.start for e in evs), max(e.end for e in evs))
        self.w0, self.w1 = window
        self.stopped = _union(_clip([(e.start, e.end) for e in host
                                     if e.name == "chipbench.audit"],
                                    self.w0, self.w1))
        self._stop_starts = [s for s, _ in self.stopped]
        self.devices = sorted(ops)

    # -- loading -----------------------------------------------------------
    @classmethod
    def from_dir(cls, path: str) -> "Trace":
        files = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                 recursive=True))
        if not files:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        return cls.from_file(files[-1])

    @classmethod
    def from_file(cls, path: str) -> "Trace":
        from jax.profiler import ProfileData
        return cls.from_profile(ProfileData.from_file(path))

    @classmethod
    def from_profile(cls, pdata) -> "Trace":
        ops: Dict[int, List[Event]] = {}
        modules: Dict[int, List[Event]] = {}
        host: List[Event] = []
        for plane in pdata.planes:
            m = DEVICE.match(plane.name)
            for line in plane.lines:
                if m:
                    dev = int(m.group(1))
                    if line.name == "XLA Ops":
                        ops.setdefault(dev, []).extend(
                            Event(e.name, e.start_ns, e.end_ns)
                            for e in line.events)
                    elif line.name == "XLA Modules":
                        modules.setdefault(dev, []).extend(
                            Event(e.name, e.start_ns, e.end_ns)
                            for e in line.events)
                elif plane.name.startswith("/host"):
                    host.extend(Event(e.name, e.start_ns, e.end_ns)
                                for e in line.events
                                if e.name.startswith("chipbench."))
        if not ops:
            raise ValueError("the trace holds no TPU operations")
        return cls(ops, modules, host)

    # -- reductions ----------------------------------------------------------
    @property
    def window_s(self) -> float:
        return (self.w1 - self.w0 - _length(self.stopped)) / 1e9

    def _busy(self, dev: int):
        busy = _union(_clip([(e.start, e.end) for e in self.ops[dev]],
                            self.w0, self.w1))
        return _subtract(busy, self.stopped)

    def busy_s(self) -> float:
        """Seconds with an operation running, averaged over the devices."""
        return float(np.mean([_length(self._busy(d)) for d in self.devices])
                     ) / 1e9

    def _in_window(self, events: List[Event]) -> List[Event]:
        """Events that start in the window, outside its stopped spans."""
        return [e for e in events if self.w0 <= e.start < self.w1
                and not self._is_stopped(e.start)]

    def _is_stopped(self, t: int) -> bool:
        i = bisect.bisect_right(self._stop_starts, t) - 1
        return i >= 0 and t < self.stopped[i][1]

    def op_events(self, match: Callable[[Event], bool],
                  dev: Optional[int] = None) -> List[Event]:
        devs = self.devices if dev is None else [dev]
        return [e for d in devs for e in self._in_window(self.ops[d])
                if match(e)]

    def module_runs(self) -> List[Event]:
        """Program runs in the window, on every device."""
        return [e for d in self.devices
                for e in self._in_window(self.modules.get(d, []))]

    def runs_with_op(self, match: Callable[[Event], bool]) -> List[Event]:
        """Program runs during which a matching operation starts on the
        same device."""
        out = []
        for d in self.devices:
            starts = sorted(e.start for e in self.ops[d] if match(e))
            for m in self._in_window(self.modules.get(d, [])):
                i = bisect.bisect_left(starts, m.start)
                if i < len(starts) and starts[i] < m.end:
                    out.append(m)
        return out

    def in_spans(self, events: List[Event], names) -> List[Event]:
        """The events that start inside a host span of one of ``names``."""
        spans = sorted((h.start, h.end) for h in self.host
                       if h.name in names)
        starts = [s for s, _ in spans]
        out = []
        for e in events:
            i = bisect.bisect_right(starts, e.start) - 1
            if i >= 0 and e.start < spans[i][1]:
                out.append(e)
        return out

    def module_seconds(self, name: str) -> Tuple[float, int]:
        """Device time and run count of program ``name`` (``jit_<fn>``),
        averaged over the devices."""
        evs = [e for d in self.devices
               for e in self._in_window(self.modules.get(d, []))
               if e.name.split("(")[0] == name]
        return (sum(e.dur for e in evs) / 1e9 / len(self.devices),
                len(evs) // len(self.devices))

    def exposed_s(self, match: Callable[[Event], bool]) -> float:
        """Seconds in which a matching operation (a collective) runs and no
        other operation does, averaged over the devices."""
        total = 0
        for d in self.devices:
            evs = self._in_window(self.ops[d])
            mine = _union([(e.start, e.end) for e in evs if match(e)])
            rest = _union([(e.start, e.end) for e in evs if not match(e)])
            total += _length(_subtract(mine, rest))
        return total / 1e9 / len(self.devices)

    def breakdown(self, top: int = 10) -> Dict[str, List]:
        """The device operations that took most time, and the idle time on
        device 0 by the benchmark span the host was in."""
        by_op: Dict[str, int] = collections.Counter()
        for e in self.op_events(lambda e: True):
            # an op's name is its HLO text: keep the name and result shape
            by_op[e.name.split("{")[0][:120]] += e.dur
        n = len(self.devices)
        ops = [[k, v / 1e9 / n] for k, v in by_op.most_common(top)]
        busy = self._busy(self.devices[0])
        gaps, prev = [], self.w0
        for s, e in busy + [(self.w1, self.w1)]:
            if s > prev:
                gaps.append((prev, s))
            prev = max(prev, e)
        gaps = _subtract(gaps, self.stopped)
        spans = sorted(self.host, key=lambda e: e.start)
        by_span: Dict[str, int] = collections.Counter()
        for s, e in gaps:
            mid = (s + e) // 2
            inner = [h for h in spans if h.start <= mid < h.end]
            name = min(inner, key=lambda h: h.dur).name if inner else "none"
            by_span[name] += e - s
        idle = [[k, v / 1e9] for k, v in by_span.most_common(top)]
        return {"device_ops": ops, "idle_gaps": idle}


def _subtract(a, b):
    """Intervals ``a`` less the union ``b`` (both sorted, disjoint)."""
    out = []
    for s, e in a:
        for bs, be in b:
            if be <= s or bs >= e:
                continue
            if bs > s:
                out.append((s, bs))
            s = max(s, be)
            if s >= e:
                break
        if s < e:
            out.append((s, e))
    return out
