"""Device time per step of pressure reclamation: the program runs that run
the compaction kernel (``reclaim_on_pressure``: ring flush, hot-first
compaction, cold spill, page sweep)."""
from chipbench.metrics_util import per_step_ms, reclaim_runs


def read(run, trace, peaks):
    return per_step_ms(run, trace, reclaim_runs(trace))
