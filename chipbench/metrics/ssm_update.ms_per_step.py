"""Device milliseconds per decode step of the fused Mamba-2 state update
(``%ssm_update``, one run per Mamba layer per step), averaged over the
devices."""
from chipbench.metrics_util import kernel_runs


def read(run, trace, peaks):
    evs, steps = kernel_runs(trace, "ssm_update"), run.obs.get("decode_ctx")
    if not evs or not steps:
        return None
    return sum(e.dur for e in evs) / 1e6 / len(trace.devices) / len(steps)
