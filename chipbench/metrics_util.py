"""Helpers the metric readers share."""
from __future__ import annotations

import re
from typing import Optional


def is_kernel(event, kernel: str) -> bool:
    """Whether a device operation is a run of Pallas kernel ``kernel``:
    its HLO name is ``%<kernel>.<n>``."""
    return re.match(rf"%{re.escape(kernel)}(\.\d+)? = ", event.name) \
        is not None


_COLLECTIVE = re.compile(
    r"%(collective-permute|all-reduce|all-gather|reduce-scatter|all-to-all)"
    r"[-.\w]* = ")


def is_collective(event) -> bool:
    """Whether a device operation moves data between chips."""
    return _COLLECTIVE.match(event.name) is not None


def kernel_runs(trace, kernel: str):
    """Device runs of one Pallas kernel in the window."""
    return trace.op_events(lambda e: is_kernel(e, kernel))


def reclaim_runs(trace):
    """Program runs that run the compaction kernel: the pressure
    reclamation passes."""
    return trace.runs_with_op(lambda e: is_kernel(e, "compact"))


def per_step_ms(run, trace, events) -> Optional[float]:
    """Device milliseconds per step of the window in ``events``, averaged
    over the devices; None when there are none."""
    steps = run.obs.get("steps")
    if not events or not steps:
        return None
    return 1e3 * sum(e.dur for e in events) / 1e9 / len(trace.devices) / steps


_SHAPE = re.compile(r"s32\[(\d+),(\d+)\]")


def first_shape(event):
    """``(rows, cols)`` of the first 2-D int32 shape in an operation's HLO
    name (its first result); None when it names none."""
    m = _SHAPE.search(event.name)
    return (int(m.group(1)), int(m.group(2))) if m else None
