"""Pages held (not free) over pages needed (referenced within its length
by the current table or a pinned lane's), time-averaged over the audits of
the window: the space the collector keeps per live page."""
import numpy as np


def read(run, trace, peaks):
    held = run.obs.get("held_ratio")
    return float(np.mean(held)) if held else None
