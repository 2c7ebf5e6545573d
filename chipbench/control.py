#!/usr/bin/env python3
"""Readings that set a cell's limits: the program's compared numbers and
its control's, over many seeds in one process.  The benchmark's own runs
never run this.

    python chipbench/control.py --workload <name> --seeds 1,2,3 \\
        --seconds 5 [--control-only]

For a served model the control is the float32 reference with every weight
rounded to float8 (e4m3), the precision below the bfloat16 the
configuration serves in: at each position of the sampled requests it picks
its own best token, and the gap of that pick under the float32 reference
is held to the cell's limit (`drivers.serve.control`).  For the paged
cache, which states no precision, the control breaks the guarantee the
cache states: a reader's pin is not announced, so the collector may
recycle what the reader still reads; its run is compared as any other.
Each seed prints one JSON line: the program's checks and ``correct``, and
the control's.  ``--control-only`` skips the program's own run where the
control does not need it (the paged cache, whose limits are all 0).
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional

sys.path[:0] = [str(Path(__file__).resolve().parents[1])]
from chipbench.run import (  # noqa: E402
    HERE, enable_cache, load_cell, load_module)


def _program(cell, workload, seed, seconds):
    from chipbench import harness
    r = harness.Run(workload=workload, config=cell["config"],
                    traffic=cell["traffic"], seed=seed, seconds=seconds,
                    trace=False, chips=int(cell["cell"]["chips"]),
                    t_start=time.perf_counter(), limits=cell["limits"])
    driver = load_module(HERE / "drivers" / f"{cell['traffic']['engine']}.py")
    driver.run(r)
    return r, driver


def readings(cell, workload: str, seed: int, seconds: float,
             program: bool = True) -> Dict:
    """One seed: the program's checks, then its control's."""
    out: Dict = {"seed": seed}
    serve = cell["traffic"]["engine"] == "serve"
    if program or serve:
        r, driver = _program(cell, workload, seed, seconds)
        out["program"] = {k: c.value for k, c in r.checks.items()}
        out["program_correct"] = r.correct
    if serve:
        c = driver.control(r)
    else:
        if int(cell["traffic"].get("hosts", 1)) > 1:
            from repro.dist.mvgc import ShardedPagedKVEngine as engine

            def unannounced(self, host, lane):
                return int(self.st.mv.now[host])
        else:
            from repro.serve.engine import PagedKVEngine as engine

            def unannounced(self, lane):
                return int(self.st.mv.now)
        real = engine.pin
        engine.pin = unannounced
        try:
            c, _ = _program(cell, workload, seed, seconds)
        finally:
            engine.pin = real
    out["control"] = {k: v.value for k, v in c.checks.items()}
    out["control_correct"] = c.correct
    return out


def main(argv: Optional[List[str]] = None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--control-only", action="store_true")
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("chipbench: no TPU here", file=sys.stderr)
        return 2
    enable_cache()
    for seed in [int(s) for s in args.seeds.split(",")]:
        print(json.dumps(readings(cell, args.workload, seed, args.seconds,
                                  program=not args.control_only)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
