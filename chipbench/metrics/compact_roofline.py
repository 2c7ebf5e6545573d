"""Bytes the fused compaction kernel needs for each of its runs in the
window (`chipbench.counts.compact_bytes`, from the rows and versions its
HLO names) at the chip's HBM bandwidth, over the kernel's device time."""
from chipbench import counts
from chipbench.metrics_util import first_shape, kernel_runs


def read(run, trace, peaks):
    evs = kernel_runs(trace, "compact")
    shapes = [first_shape(e) for e in evs]
    if not evs or None in shapes:
        return None
    need = sum(counts.compact_bytes(r, v) for r, v in shapes)
    secs = sum(e.dur for e in evs) / 1e9
    return 100.0 * need / peaks["hbm_bytes_per_s"] / secs
