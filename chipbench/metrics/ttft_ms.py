"""Mean over every request of the window of its first token's time less
the time it was due (a closed loop: a wave's requests are all due at its
start)."""
import numpy as np


def read(run, trace, peaks):
    ttft = run.obs.get("ttft_s")
    return float(np.mean(ttft)) * 1e3 if ttft else None
