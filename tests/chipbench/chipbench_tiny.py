"""CPU-sized cells for the benchmark's tests: the configuration and mixes
under ``data/``, run through ``chipbench.run.execute`` with the chip look
skipped."""
from __future__ import annotations

import io
import json
import os
from contextlib import redirect_stdout
from typing import Dict

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
E2E = ["tokens_per_s", "gap_p95_ms", "ttft_ms", "setup_s"]
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def load(name: str) -> Dict:
    with open(os.path.join(DATA, name)) as f:
        return json.load(f)


def cell(engine: str) -> Dict:
    return {"cell": {"name": f"tiny.{engine}", "chips": 1},
            "config": load("tiny.json"),
            "traffic": load(f"tiny-{engine}.json"),
            "limits": {"served_logit_gap": 0.02},
            "end_to_end": [{"name": n, "unit": "u"} for n in E2E],
            "per_layer": []}


def run(c: Dict, seed: int = 2 ** 33 + 7, seconds: float = 1.0,
        execute=None) -> Dict:
    """One run on the CPU; returns the result line."""
    import jax
    if execute is None:
        from chipbench.run import execute
    out = io.StringIO()
    with redirect_stdout(out):
        rc = execute(c, workload=c["cell"]["name"], seed=seed,
                     seconds=seconds, trace=False,
                     devices=jax.devices()[:1], peaks=PEAKS)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])
