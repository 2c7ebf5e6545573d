"""95th percentile of the wall time of every step in the window, each
ended in ``block_until_ready``: the gap between two tokens of a sequence."""
from chipbench.harness import p95


def read(run, trace, peaks):
    gaps = run.obs.get("gaps_s")
    return p95(gaps) * 1e3 if gaps else None
