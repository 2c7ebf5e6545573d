"""The sharded paged cell at a CPU size (one device holds every shard; the
protocol does not depend on placement): a sound run is correct, and the
timed path broken underneath makes ``correct`` come out false."""
from __future__ import annotations

import jax.numpy as jnp
import pytest

import chipbench_tiny as tiny


@pytest.fixture(scope="module")
def cell():
    return tiny.cell("sharded")


def test_sharded_sound_run_is_correct(cell):
    res = tiny.run(cell, seconds=2.0)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["metrics"]["tokens_per_s"]["value"]


def _step_unchanged(real):
    def step(self, seq_ids, k, v, mask):
        return jnp.zeros(mask.shape, bool)
    return step


def _half_batch(real):
    def step(self, seq_ids, k, v, mask):
        half = jnp.arange(mask.shape[-1]) % 2 == 0
        return real(self, seq_ids, k, v, mask & half) & half
    return step


def _token_altered(real):
    def step(self, seq_ids, k, v, mask):
        return real(self, seq_ids, k.at[:, :, 0, 0].add(1.0), v, mask)
    return step


@pytest.mark.parametrize("fault", [_step_unchanged, _half_batch,
                                   _token_altered])
def test_sharded_fault_is_not_correct(cell, monkeypatch, fault):
    from repro.dist.mvgc import ShardedPagedKVEngine
    monkeypatch.setattr(ShardedPagedKVEngine, "step",
                        fault(ShardedPagedKVEngine.step))
    res = tiny.run(cell, seconds=2.0)
    assert not res["correct"], res["checks"]


def _ring_left_out(contrib, ring=None):
    # each GC refresh takes host 0's own oldest pin: no exchange
    return jnp.asarray(contrib)[0]


def _aging_left_out(contrib, ages_s, budget_s):
    return jnp.asarray(contrib, jnp.int32), jnp.int32(0)


@pytest.mark.parametrize("name,fault", [("global_lwm", _ring_left_out),
                                        ("age_out_stale", _aging_left_out)])
def test_sharded_lwm_fault_is_not_correct(cell, monkeypatch, name, fault):
    """The exchange between chips left out, or the stalled host never aged
    out: the global LWM the shards use departs from the reference's."""
    from repro.dist import mvgc
    monkeypatch.setattr(mvgc, name, fault)
    res = tiny.run(cell, seconds=2.0)
    assert not res["correct"], res["checks"]
    assert res["checks"]["global_lwm_wrong"]["value"] > 0


def test_sharded_control_unannounced_pins_fail(cell, monkeypatch):
    """The control: a reader whose pin is not announced on its host."""
    from repro.dist.mvgc import ShardedPagedKVEngine
    monkeypatch.setattr(ShardedPagedKVEngine, "pin",
                        lambda self, host, lane: int(self.st.mv.now[host]))
    res = tiny.run(cell, seconds=2.0)
    assert not res["correct"], res["checks"]
