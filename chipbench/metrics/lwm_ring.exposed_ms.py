"""Device milliseconds per step in which the global low-water mark's ring
all-reduce (``dist/mvgc`` through ``dist/overlap``: collective permutes
between the chips) runs with no other operation beside it, averaged over
the chips."""
from chipbench.metrics_util import is_collective


def read(run, trace, peaks):
    steps = run.obs.get("steps")
    if len(trace.devices) < 2 or not steps or not trace.op_events(
            is_collective):
        return None
    return 1e3 * trace.exposed_s(is_collective) / steps
