"""The least bytes the window's decode steps need (every weight once, K/V
up to each sequence's length, the descriptor store; `chipbench.counts`) at
the chip's HBM bandwidth, over the device time of the decode program
(``jit_decode``).  Decode at this batch is bound by bytes, not FLOPs."""
from chipbench import counts


def read(run, trace, peaks):
    o, cfg = run.obs, run.config
    secs, runs = trace.module_seconds("jit_decode")
    if not runs or not o.get("decode_ctx"):
        return None
    sh = o["shapes"]
    need = sum(counts.decode_min_bytes(cfg, [c] * sh["batch"], sh["slots"],
                                       sh["versions"], sh["lanes"])
               for c in o["decode_ctx"])
    # the traced window holds exactly the window's decode steps
    if runs != len(o["decode_ctx"]):
        return None
    return 100.0 * need / peaks["hbm_bytes_per_s"] / secs
