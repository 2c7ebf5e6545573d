"""The hybrid served cell's driver at a CPU size (``data/tiny-hybrid.json``,
one period of 5 Mamba-2, 1 attention and 4 Mamba-2 layers, under
``data/tiny-serve_hybrid.json``), run whole with the chip look skipped: a
sound run is ``correct``, each fault planted in the recurrent path makes it
not ``correct``, and so does the float8 control.

The limit on ``served_logit_gap`` here is 0.0015: over 6 seeds and two
window lengths sound runs read 0.0001 to 0.0006 (bfloat16 weights and
cache), the control 0.0044 to 0.0094, and the faults above 0.01."""
from __future__ import annotations

import jax.numpy as jnp
import pytest

import chipbench_tiny as tiny

LIMIT = 0.0015


@pytest.fixture(scope="module")
def hybrid_cell():
    return {"cell": {"name": "tiny.serve_hybrid", "chips": 1},
            "config": tiny.load("tiny-hybrid.json"),
            "traffic": tiny.load("tiny-serve_hybrid.json"),
            "limits": {"served_logit_gap": LIMIT},
            "end_to_end": [{"name": n, "unit": "u"} for n in tiny.E2E],
            "per_layer": []}


def test_hybrid_sound_run_is_correct(hybrid_cell):
    res = tiny.run(hybrid_cell)
    assert res["correct"], res["checks"]
    assert res["metrics"]["tokens_per_s"]["value"] > 0


def _state_unchanged(monkeypatch):
    """Decode reads the state but leaves it as it was."""
    from repro.kernels.ssm_update import ops
    real = ops.ssm_update

    def frozen(state, *args, **kw):
        y, _ = real(state, *args, **kw)
        return y, state
    monkeypatch.setattr(ops, "ssm_update", frozen)


def _conv_window_dropped(monkeypatch):
    """Prefill leaves an empty conv window in the cache."""
    from repro.models import blocks
    real = blocks.mamba2

    def dropped(*args, **kw):
        out, st = real(*args, **kw)
        return out, st._replace(conv=jnp.zeros_like(st.conv))
    monkeypatch.setattr(blocks, "mamba2", dropped)


@pytest.mark.parametrize("fault", [_state_unchanged, _conv_window_dropped])
def test_hybrid_fault_is_not_correct(hybrid_cell, monkeypatch, fault):
    fault(monkeypatch)
    res = tiny.run(hybrid_cell)
    assert not res["correct"], res["checks"]


def test_hybrid_control_fp8_reads_wider_gap(hybrid_cell):
    from chipbench import harness
    from chipbench.drivers import serve_hybrid
    c = hybrid_cell
    r = harness.Run(workload=c["cell"]["name"], config=c["config"],
                    traffic=c["traffic"], seed=5, seconds=1.0, trace=False,
                    chips=1, t_start=0.0, limits=c["limits"])
    serve_hybrid.run(r)
    assert r.correct, r.checks
    assert r.obs["space"]["recurrent_state_bytes"] == 9 * 4 * 16 * 64 * 2
    ctl = serve_hybrid.control(r)
    program = r.checks["served_logit_gap"].value
    control = ctl.checks["served_logit_gap"]
    assert control.value > 3 * program, (program, control)
    assert not ctl.correct, ctl.checks


def test_hybrid_readers_on_a_made_trace():
    """The four hybrid readers on a trace made by hand: one decode step of
    the cell in a 14 ms ``jit_decode``, where each of 36 layers slices its
    state out of the cache (45 us), runs ``%ssm_update`` (50 us) and writes
    the state back (51 us), and one prefill, in a 3 s window; ops outside
    the decode program do not count, and without a kernel run the readers
    read nothing."""
    import json
    import os

    from chipbench import counts_hybrid, harness
    from chipbench.run import HERE, load_module
    from chipbench.trace import Event, Trace

    root = os.path.dirname(HERE)
    with open(os.path.join(root, "chipbench", "configs",
                           "granite-4.0-h-micro.json")) as f:
        cfg = json.load(f)
    r = harness.Run(workload="w", config=cfg, traffic={}, seed=1,
                    seconds=1.0, trace=True, chips=1, t_start=0.0)
    r.obs.update({"decode_ctx": [1025], "prefills": 1, "shapes": {
        "batch": 32, "prompt_len": 1024, "slots": 32, "versions": 16,
        "lanes": 8}})
    us, ms = 1000, 1_000_000
    ops, t = [], ms
    for i in range(36):
        for name, dur in (
                ("%dynamic-slice_bitcast_fusion.2 = bf16[32,128,4096]", 45),
                (f"%ssm_update.{i} = (f32[32,1,4096], bf16[32,128,4096])",
                 50),
                ("%bitcast_dynamic-update-slice_fusion.4 = "
                 "bf16[4,32,128,4096]", 51)):
            ops.append(Event(name + " fusion()", t, t + dur * us))
            t += dur * us
    # the prefill's own write of the state: outside jit_decode
    ops.append(Event("%dynamic-update-slice_fusion.9 = bf16[4,32,128,4096]",
                     20 * ms, 21 * ms))
    trace = Trace({0: ops}, {0: [Event("jit_decode(7)", ms, 15 * ms),
                                 Event("jit_prefill(8)", 16 * ms, 30 * ms)]},
                  [], window=(0, 3000 * ms))
    peaks = tiny.PEAKS

    def read(name, t):
        return load_module(HERE / "metrics" / f"{name}.py").read(r, t, peaks)

    assert read("ssm_update.ms_per_step", trace) == pytest.approx(1.8)
    want = 36 * counts_hybrid.ssm_update_bytes(cfg, 32) / 819e9 / 5.256e-3
    assert read("ssm_update_roofline", trace) == pytest.approx(100 * want)
    step = counts_hybrid.decode_min_bytes(cfg, [1025] * 32, 32, 16, 8)
    assert read("hybrid_decode_roofline", trace) == pytest.approx(
        100 * step / 819e9 / 14e-3)
    flops = (counts_hybrid.prefill_flops(cfg, 32, 1024)
             + counts_hybrid.decode_flops(cfg, [1025] * 32))
    assert read("hybrid_step.mfu", trace) == pytest.approx(
        100 * flops / 197e12 / 3)
    for name in ("ssm_update.ms_per_step", "ssm_update_roofline",
                 "hybrid_decode_roofline", "hybrid_step.mfu"):
        assert 0 < read(name, trace) < 100, name
    empty = Trace({0: [Event("%fusion.1 = f32[]", 0, ms)]}, {0: []}, [],
                  window=(0, 1000 * ms))
    assert read("ssm_update_roofline", empty) is None
    assert read("ssm_update.ms_per_step", empty) is None
    assert read("hybrid_decode_roofline", empty) is None
