"""The least bytes the window's decode steps of a Mamba-2/attention hybrid
need (every weight once, the recurrent state and conv window read and
written, K/V up to each sequence's length, the descriptor store;
`chipbench.counts_hybrid`) at the chip's HBM bandwidth, over the device
time of the decode program (``jit_decode``)."""
from chipbench import counts_hybrid


def read(run, trace, peaks):
    o, cfg = run.obs, run.config
    secs, runs = trace.module_seconds("jit_decode")
    # the traced window holds exactly the window's decode steps
    if not runs or not o.get("decode_ctx") or runs != len(o["decode_ctx"]):
        return None
    sh = o["shapes"]
    need = sum(counts_hybrid.decode_min_bytes(
        cfg, [c] * sh["batch"], sh["slots"], sh["versions"], sh["lanes"])
        for c in o["decode_ctx"])
    return 100.0 * need / peaks["hbm_bytes_per_s"] / secs
