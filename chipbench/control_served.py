#!/usr/bin/env python3
"""Readings that set a served cell's limits, for a driver with a
``control`` of its own (`chipbench.drivers.serve_hybrid`): the program's
compared numbers and its control's, over many seeds in one process.  The
benchmark's own runs never run this.

    python chipbench/control_served.py --workload <name> --seeds 1,2,3 \\
        --seconds 5

For each seed the cell's driver runs the program as a benchmark run does;
its ``control`` then recomputes the compared numbers with the float32
reference rounded to float8 (e4m3) standing in for the served tokens, held
to the same limits.  Each seed prints one JSON line: the program's checks
and ``correct``, and the control's.
"""
from __future__ import annotations

import json
import sys
from pathlib import Path
from typing import List, Optional

sys.path[:0] = [str(Path(__file__).resolve().parents[1])]
from chipbench.control import _program  # noqa: E402
from chipbench.run import enable_cache, load_cell  # noqa: E402


def main(argv: Optional[List[str]] = None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("chipbench: no TPU here", file=sys.stderr)
        return 2
    enable_cache()
    for seed in [int(s) for s in args.seeds.split(",")]:
        r, driver = _program(cell, args.workload, seed, args.seconds)
        c = driver.control(r)
        print(json.dumps({
            "seed": seed,
            "program": {k: v.value for k, v in r.checks.items()},
            "program_correct": r.correct,
            "control": {k: v.value for k, v in c.checks.items()},
            "control_correct": c.correct,
            "space": r.obs.get("space"),
            "memory_peak_bytes": r.obs["device"]["memory_peak_bytes"],
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
