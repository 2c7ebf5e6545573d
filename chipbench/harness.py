"""What every cell shares: the run's context, the measured window, the
numbers compared for ``correct``, and the result line.

A driver (``chipbench/drivers/<engine>.py``) gets a `Run`, builds and warms
its engine, measures inside a `Window`, and fills ``run.obs`` (counts and
host-clock samples that the metric readers take) and ``run.checks``.
"""
from __future__ import annotations

import dataclasses
import json
import math
import sys
import time
from contextlib import contextmanager
from typing import Any, Dict, Optional

import numpy as np


@dataclasses.dataclass
class Check:
    """One number compared for ``correct``: it passes while ``value <=
    limit``."""
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(self.value <= self.limit) and not math.isnan(self.value)


@dataclasses.dataclass
class Run:
    """One run of one cell."""
    workload: str
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    seed: int
    seconds: float
    trace: bool
    chips: int
    t_start: float                      # process start, host clock
    limits: Dict[str, float] = dataclasses.field(default_factory=dict)
    trace_dir: Optional[str] = None
    setup_s: Optional[float] = None
    attempted: int = 0
    failed: int = 0
    obs: Dict[str, Any] = dataclasses.field(default_factory=dict)
    checks: Dict[str, Check] = dataclasses.field(default_factory=dict)

    def setup_done(self) -> None:
        """Set-up ends here: the next thing is the first timed step."""
        self.setup_s = time.perf_counter() - self.t_start

    def check(self, name: str, value: float, limit: float) -> None:
        self.checks[name] = Check(float(value), float(limit))

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(c.ok for c in self.checks.values())


_COMPILES = [0]


def _count_compile(event: str, **_) -> None:
    if event == "/jax/compilation_cache/compile_requests_use_cache":
        _COMPILES[0] += 1


class Window:
    """The measured window: a host clock that can be stopped (for the
    accounting audits, which are not part of the served work) and, in a
    traced run, the profiler around it.  It counts the programs compiled
    (or loaded from the compile cache) inside it, which should be none."""

    def __init__(self, run: Run):
        self.run = run
        self.paused_s = 0.0
        self.t0 = self.t1 = None

    def __enter__(self) -> "Window":
        import jax
        if not getattr(Window, "_listening", False):
            jax.monitoring.register_event_listener(_count_compile)
            Window._listening = True
        self._compiles0 = _COMPILES[0]
        if self.run.trace:
            # host spans and device events only: the Python call tracer
            # writes several times more events than the device does
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.run.trace_dir,
                                     profiler_options=opts)
        self.t0 = time.perf_counter()
        self._ann = span("chipbench.window")
        self._ann.__enter__()
        return self

    def __exit__(self, *exc) -> None:
        self._ann.__exit__(*exc)
        self.t1 = time.perf_counter()
        self.run.obs["compiles_in_window"] = _COMPILES[0] - self._compiles0
        if self.run.trace:
            import jax
            jax.profiler.stop_trace()

    def elapsed(self) -> float:
        """Measured seconds so far, stopped time left out."""
        end = self.t1 if self.t1 is not None else time.perf_counter()
        return end - self.t0 - self.paused_s

    @contextmanager
    def stopped(self):
        t = time.perf_counter()
        try:
            with span("chipbench.audit"):
                yield
        finally:
            self.paused_s += time.perf_counter() - t


def span(name: str):
    """A host span in the profiler's trace (free when no trace is taken)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


def p95(samples) -> float:
    """95th percentile, linear between order statistics."""
    return float(np.percentile(np.asarray(samples, np.float64), 95))


def device_info(devices) -> Dict[str, Any]:
    d = devices[0]
    peak = 0
    for dev in devices:
        stats = dev.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def emit(run: Run, metrics: Dict[str, Dict[str, Any]], device: Dict,
         breakdown: Optional[Dict] = None) -> None:
    """The compared numbers as the last lines of stderr, then the result as
    the last line of stdout with the checks under the last key."""
    print(f"compiles_in_window {run.obs.get('compiles_in_window')}",
          file=sys.stderr)
    for name, c in run.checks.items():
        print(f"check {name} {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAIL'}", file=sys.stderr)
    sys.stderr.flush()
    out: Dict[str, Any] = {
        "correct": run.correct,
        "attempted": int(run.attempted),
        "failed": int(run.failed),
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {k: {"value": c.value, "limit": c.limit}
                     for k, c in run.checks.items()}
    print(json.dumps(out), flush=True)
