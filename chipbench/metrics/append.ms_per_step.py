"""Device time per step of the page pool: the program runs the engine
starts inside the benchmark's append and reset spans (``append_tokens`` and
its retries, ``reset_sequence``, the pressure gate), less the reclamation
passes and the traffic's own K/V generation."""
from chipbench.metrics_util import per_step_ms, reclaim_runs


def read(run, trace, peaks):
    reclaim = {id(e) for e in reclaim_runs(trace)}
    runs = [e for e in trace.in_spans(trace.module_runs(),
                                      {"chipbench.append", "chipbench.reset"})
            if id(e) not in reclaim and not e.name.startswith("jit_kv_step")]
    return per_step_ms(run, trace, runs)
