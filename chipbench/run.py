#!/usr/bin/env python3
"""One run of one benchmark cell, on the chips of the machine it starts on.

    python chipbench/run.py --workload <name> --seed <n> --seconds <s> \\
        --trace <0|1>

Everything is found by the names in ``BENCHMARK.json``: the cell gives a
configuration (``configs[].file``) and a traffic mix
(``chipbench/traffic/<traffic>.json``), whose ``engine`` names the driver
(``chipbench/drivers/<engine>.py``); each metric is read by
``chipbench/metrics/<name>.py``; the limits of the numbers a cell compares
for ``correct`` are in ``chipbench/limits/<workload>.json``.  With ``--trace 0`` the run reports the
cell's end-to-end metrics; with ``--trace 1`` it takes a profiler trace of
the window and reports the cell's per-layer metrics.

Without a TPU, or with fewer chips than the cell asks for, it exits with
code 2 before any work and prints no result.  The last line of standard
output is the result, one JSON object; the numbers compared for
``correct`` are the last lines of standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
HERE = ROOT / "chipbench"
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def load_module(path: Path):
    """Import one file by its path (metric names hold dots)."""
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str, root: Path = ROOT) -> Dict[str, Any]:
    """The cell ``name`` with its configuration, mix and metrics."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {c["name"]: c for c in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]

    def applies(m):
        return name in m.get("workloads", [name])

    limits = root / "chipbench" / "limits" / f"{name}.json"
    return {
        "limits": json.loads(limits.read_text()) if limits.exists() else {},
        "cell": cell,
        "config": json.loads((root / conf["file"]).read_text()),
        "traffic": json.loads(
            (root / "chipbench" / "traffic" / f"{cell['traffic']}.json")
            .read_text()),
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
    }


def read_metrics(specs: List[Dict], run, trace, peaks) -> Dict[str, Dict]:
    """Each metric's reader, found by name; one that finds nothing to read
    returns None and the metric is left out."""
    out = {}
    for m in specs:
        reader = load_module(HERE / "metrics" / f"{m['name']}.py")
        value = reader.read(run, trace, peaks)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def enable_cache() -> None:
    """The program's compile cache (a fixed directory in the checkout, or
    ``JAX_COMPILATION_CACHE_DIR``), holding every program, so that only a
    first run compiles."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def execute(cell: Dict[str, Any], *, workload: str, seed: int,
            seconds: float, trace: bool, devices, peaks,
            t_start: float = T_START) -> int:
    """Drive one run on ``devices`` and print its result."""
    from chipbench import harness

    chips = int(cell["cell"]["chips"])
    run = harness.Run(workload=workload, config=cell["config"],
                      traffic=cell["traffic"], seed=seed, seconds=seconds,
                      trace=trace, chips=chips, t_start=t_start,
                      limits=cell["limits"])
    tmp = None
    if trace:
        # the trace goes under TMPDIR, never to a fixed path
        tmp = tempfile.mkdtemp(prefix="chipbench-trace-")
        run.trace_dir = tmp
    try:
        driver = load_module(HERE / "drivers"
                             / f"{cell['traffic']['engine']}.py")
        driver.run(run)
        device = dict(run.obs.pop("device"))
        reduced = breakdown = None
        if trace:
            from chipbench.trace import Trace
            reduced = Trace.from_dir(tmp)
            device["busy_s"] = reduced.busy_s()
            device["window_s"] = reduced.window_s
            breakdown = reduced.breakdown()
            metrics = read_metrics(cell["per_layer"], run, reduced, peaks)
        else:
            metrics = read_metrics(cell["end_to_end"], run, None, peaks)
    finally:
        if tmp is not None:
            shutil.rmtree(tmp, ignore_errors=True)
    harness.emit(run, metrics, device, breakdown)
    return 0


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = load_cell(args.workload)
    chips = int(cell["cell"]["chips"])
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chipbench: no TPU here (platform {devices[0].platform}); "
              f"this benchmark runs only on the chip", file=sys.stderr)
        return 2
    if len(devices) < chips:
        print(f"chipbench: {args.workload} needs {chips} chips, found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    from chipbench import peaks as peak_table
    peaks = peak_table.lookup(devices[0].device_kind)
    enable_cache()
    return execute(cell, workload=args.workload, seed=args.seed,
                   seconds=args.seconds, trace=bool(args.trace),
                   devices=devices[:chips], peaks=peaks)


if __name__ == "__main__":
    sys.exit(main())
