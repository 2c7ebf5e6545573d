"""The timed path broken underneath a whole run (chip look skipped): every
fault a cell can have must make ``correct`` come out false, and the
controls must fail the limits the sound runs pass."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

import chipbench_tiny as tiny


@pytest.fixture(scope="module")
def paged_cell():
    return tiny.cell("paged")


@pytest.fixture(scope="module")
def serve_cell():
    return tiny.cell("serve")


def test_paged_sound_run_is_correct(paged_cell):
    res = tiny.run(paged_cell)
    assert res["correct"], res["checks"]
    assert res["metrics"]["tokens_per_s"]["value"] > 0
    assert list(res)[-1] == "checks"


def test_serve_sound_run_is_correct(serve_cell):
    res = tiny.run(serve_cell)
    assert res["correct"], res["checks"]
    assert res["checks"]["served_logit_gap"]["value"] < 0.02


def _paged_step_unchanged(real):
    def step(self, seq_ids, k, v, mask):
        return jnp.zeros(mask.shape, bool)
    return step


def _paged_half_batch(real):
    def step(self, seq_ids, k, v, mask):
        half = jnp.arange(mask.shape[0]) % 2 == 0
        return real(self, seq_ids, k, v, mask & half) & half
    return step


def _paged_token_altered(real):
    def step(self, seq_ids, k, v, mask):
        return real(self, seq_ids, k.at[:, 0, 0].add(1.0), v, mask)
    return step


@pytest.mark.parametrize("fault", [_paged_step_unchanged, _paged_half_batch,
                                   _paged_token_altered])
def test_paged_fault_is_not_correct(paged_cell, monkeypatch, fault):
    from repro.serve.engine import PagedKVEngine
    monkeypatch.setattr(PagedKVEngine, "step", fault(PagedKVEngine.step))
    res = tiny.run(paged_cell)
    assert not res["correct"], res["checks"]


def _serve_step_unchanged(real):
    def step(self):
        return self.state.last_tokens
    return step


def _serve_half_batch(real):
    def step(self):
        toks = real(self)
        half = toks.shape[0] // 2
        return toks.at[half:].set(0)
    return step


def _serve_token_altered(real):
    def step(self):
        return (real(self) + 1) % self.cfg.vocab_size
    return step


@pytest.mark.parametrize("fault", [_serve_step_unchanged, _serve_half_batch,
                                   _serve_token_altered])
def test_serve_fault_is_not_correct(serve_cell, monkeypatch, fault):
    from repro.serve.engine import MVServeEngine
    monkeypatch.setattr(MVServeEngine, "step", fault(MVServeEngine.step))
    res = tiny.run(serve_cell)
    assert not res["correct"], res["checks"]


def test_paged_control_unannounced_pins_fail(paged_cell, monkeypatch):
    """The paged control: a reader whose pin is not announced."""
    from repro.serve.engine import PagedKVEngine
    monkeypatch.setattr(PagedKVEngine, "pin",
                        lambda self, lane: int(self.st.mv.now))
    res = tiny.run(paged_cell, seconds=2.0)
    assert not res["correct"], res["checks"]


def test_serve_control_fp8_reads_wider_gap(serve_cell):
    """The served control: the reference in float8 picks tokens whose gap
    under the float32 reference is wider than the served tokens', and the
    cell's own check at its limit finds it not correct."""
    from chipbench import harness
    from chipbench.drivers import serve
    r = harness.Run(workload="tiny.serve", config=serve_cell["config"],
                    traffic=serve_cell["traffic"], seed=5, seconds=1.0,
                    trace=False, chips=1, t_start=0.0,
                    limits=serve_cell["limits"])
    serve.run(r)
    assert r.correct, r.checks
    ctl = serve.control(r)
    program = r.checks["served_logit_gap"].value
    control = ctl.checks["served_logit_gap"]
    assert control.value > 3 * program, (program, control)
    assert control.limit == serve_cell["limits"]["served_logit_gap"]
    assert not ctl.correct, ctl.checks
