"""The recurrent state's update at the chip's HBM bandwidth: the least
bytes of the window's updates (each Mamba layer's state read once and
written once, with each sequence's inputs and ``y``;
`chipbench.counts_hybrid.ssm_update_bytes`, one per run of the fused
kernel ``%ssm_update``) over the device time of the update inside the
decode program: the kernel's runs, and the ops named ``dynamic-slice`` or
``dynamic-update-slice`` whose result has the state's shape
(``bf16[B,N,H*P]``, or stacked ``bf16[R,B,N,H*P]``), which copy a layer's
state between the cache and the kernel.

The kernel alone is not bound by HBM: the compiler stages a layer's state
through on-chip memory (``S(1)`` in the kernel's operand layouts), so its
reads and writes of HBM happen in those copies.  Copies the compiler
makes asynchronous, overlapping other work, are not counted."""
import bisect
import re

from chipbench import counts_hybrid
from chipbench.metrics_util import is_kernel


def read(run, trace, peaks):
    o, cfg = run.obs, run.config
    if not o.get("decode_ctx"):
        return None
    B = o["shapes"]["batch"]
    N = cfg["mamba_d_state"]
    HP = cfg["mamba_n_heads"] * cfg["mamba_d_head"]
    staged = re.compile(rf"%[\w.-]*dynamic-(update-)?slice[\w.-]* = "
                        rf"bf16\[(\d+,)?{B},{N},{HP}\]")
    decode = sorted((m.start, m.end) for m in trace.module_runs()
                    if m.name.split("(")[0] == "jit_decode")
    starts = [s for s, _ in decode]

    def in_decode(e):
        i = bisect.bisect_right(starts, e.start) - 1
        return i >= 0 and e.start < decode[i][1]

    kernels = trace.op_events(lambda e: is_kernel(e, "ssm_update"))
    staging = trace.op_events(lambda e: staged.match(e.name) is not None)
    kernels = [e for e in kernels if in_decode(e)]
    if not kernels:
        return None
    secs = sum(e.dur for e in kernels + [e for e in staging
                                         if in_decode(e)]) / 1e9
    need = len(kernels) * counts_hybrid.ssm_update_bytes(cfg, B)
    return 100.0 * need / peaks["hbm_bytes_per_s"] / secs
