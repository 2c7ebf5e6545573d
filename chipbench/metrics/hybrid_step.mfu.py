"""Model FLOPs that the window's prefills and decode steps of a
Mamba-2/attention hybrid need (`chipbench.counts_hybrid`: projections and
MLPs, the chunked SSD's intra- and inter-chunk products, the attention
layers over their contexts), over the traced window, over the chip's
bfloat16 peak: the whole model step's share of the chip."""
from chipbench import counts_hybrid


def read(run, trace, peaks):
    o, cfg = run.obs, run.config
    if not o.get("decode_ctx"):
        return None
    sh = o["shapes"]
    flops = o["prefills"] * counts_hybrid.prefill_flops(
        cfg, sh["batch"], sh["prompt_len"])
    flops += sum(counts_hybrid.decode_flops(cfg, [c] * sh["batch"])
                 for c in o["decode_ctx"])
    return 100.0 * flops / trace.window_s / (peaks["bf16_flops"] * run.chips)
