"""Bytes the snapshot search-and-gather kernel needs for each of its runs
in the window (`chipbench.counts.search_gather_bytes`, from the queries and
row width its HLO names) at the chip's HBM bandwidth, over the kernel's
device time."""
from chipbench import counts
from chipbench.metrics_util import first_shape, kernel_runs


def read(run, trace, peaks):
    evs = kernel_runs(trace, "search_gather")
    shapes = [first_shape(e) for e in evs]
    if not evs or None in shapes:
        return None
    versions = run.obs["shapes"]["versions"]
    need = sum(counts.search_gather_bytes(b, versions, m) for b, m in shapes)
    secs = sum(e.dur for e in evs) / 1e9
    return 100.0 * need / peaks["hbm_bytes_per_s"] / secs
