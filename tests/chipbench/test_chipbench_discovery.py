"""A configuration, a traffic mix and a per-layer metric are added as new
files plus new entries in BENCHMARK.json, and the harness finds each by its
name: no file that is there is edited."""
from __future__ import annotations

import io
import json
import os
import shutil
from contextlib import redirect_stdout

import jax

import chipbench_tiny as tiny

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def test_dummy_cell_is_found_by_name(tmp_path):
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    before = {p: open(p, "rb").read() for p in
              (str(q) for q in (tmp_path / "chipbench").rglob("*.*"))}

    # new files only
    (tmp_path / "chipbench" / "configs" / "dummy.json").write_text(
        json.dumps(tiny.load("tiny.json")))
    (tmp_path / "chipbench" / "traffic" / "dummy-mix.json").write_text(
        json.dumps(tiny.load("tiny-paged.json")))
    (tmp_path / "chipbench" / "metrics" / "dummy.steps.py").write_text(
        "def read(run, trace, peaks):\n    return run.obs['steps']\n")
    # new entries only
    bench["configs"].append({"name": "dummy", "source": "a test",
                             "file": "chipbench/configs/dummy.json",
                             "reduced": [], "why": "a test"})
    bench["workloads"].append({"name": "dummy.dummy-mix", "config": "dummy",
                               "traffic": "dummy-mix", "chips": 1,
                               "why": "a test"})
    bench["per_layer"].append({"name": "dummy.steps", "unit": "steps",
                               "better": "higher", "source": "host_clock",
                               "layer": "page pool",
                               "moves": "tokens_per_s",
                               "workloads": ["dummy.dummy-mix"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    for path, data in before.items():
        assert open(path, "rb").read() == data

    from chipbench.run import load_module
    run_mod = load_module(tmp_path / "chipbench" / "run.py")
    cell = run_mod.load_cell("dummy.dummy-mix", root=tmp_path)
    assert [m["name"] for m in cell["per_layer"]][-1] == "dummy.steps"
    res = tiny.run(cell, execute=run_mod.execute)
    assert res["correct"], res["checks"]
    # the end-to-end metrics that name no cells are every cell's
    assert set(res["metrics"]) == {m["name"] for m in bench["end_to_end"]
                                   if "workloads" not in m}

    # the new metric is read by its own file (here without a trace)
    from chipbench import harness
    r = harness.Run(workload="dummy.dummy-mix", config=cell["config"],
                    traffic=cell["traffic"], seed=3, seconds=0.5,
                    trace=False, chips=1, t_start=0.0)
    r.obs["steps"] = 17
    got = run_mod.read_metrics(cell["per_layer"][-1:], r, None, tiny.PEAKS)
    assert got == {"dummy.steps": {"value": 17.0, "unit": "steps"}}
