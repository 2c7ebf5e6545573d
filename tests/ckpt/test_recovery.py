"""Crash-recovery tests for the checkpoint-coupled serving engine
(DESIGN.md §14): save -> drop the engine -> restore must be byte-identical
(device pytree including the retire ring and announce board, pinned snapshot
views, host-side GC counters and fork DAG), and a restored engine must be
able to evict checkpointed sole-survivor versions that an un-checkpointed
control provably cannot free."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

jax.config.update("jax_platform_name", "cpu")

from repro.core.telemetry import GCConfig
from repro.serve import forking
from repro.serve.engine import PagedKVEngine

B, PAGES, PS, MP, V = 8, 20, 4, 6, 8
IDLE = 5          # seqs 0..4 go idle after warmup; 5..7 keep decoding
KV_HEADS, HEAD_DIM = 1, 4


def mk(policy="slrt"):
    return PagedKVEngine(
        B, PAGES, PS, MP, KV_HEADS, HEAD_DIM,
        gc=GCConfig(policy=policy, versions_per_slot=V, reader_lanes=4,
                    hot_k=B),
        dtype=jnp.float32)


def step(eng, mask, val):
    """One decode step with per-(step, seq) distinct values so recycled
    pages change content."""
    base = np.arange(B, dtype=np.float32) + B * val
    kv = jnp.asarray(np.broadcast_to(base[:, None, None],
                                     (B, KV_HEADS, HEAD_DIM)))
    return eng.step(jnp.arange(B, dtype=jnp.int32), kv, kv,
                    jnp.asarray(mask))


def current_sig(eng, seqs):
    """Exact content fingerprint of the named sequences' current views."""
    tbl, ln = eng.view_at(2**31 - 2)
    tbl, ln = np.asarray(tbl), np.asarray(ln)
    return tuple(
        (int(ln[s]),) + forking.prefix_values(eng.st, tbl[s], int(ln[s]))
        for s in seqs)


def warmup(eng, steps=8):
    all_mask = np.ones((B,), bool)
    for i in range(steps):
        failed = step(eng, all_mask, i + 1)
        assert not np.asarray(failed).any()


def assert_trees_equal(a, b):
    leaves_a, treedef_a = jax.tree_util.tree_flatten(a)
    leaves_b, treedef_b = jax.tree_util.tree_flatten(b)
    assert treedef_a == treedef_b
    for la, lb in zip(leaves_a, leaves_b):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


def test_checkpoint_restore_roundtrip_byte_identical(tmp_path):
    """save -> drop engine -> restore: the full device pytree (version
    store, retire ring, announce board, page tables, KV pages, bitmaps) and
    the host GC state come back byte-identical; a pinned snapshot resolves
    to the same bytes through the restored engine."""
    eng = mk()
    warmup(eng)
    # fork a lineage edge and pin a reader so both survive the round-trip
    assert not np.asarray(eng.fork(
        jnp.asarray([5], jnp.int32), jnp.asarray([0], jnp.int32),
        jnp.ones((1,), bool))).any()
    lane_ts = eng.pin(0)
    want_view = current_sig(eng, range(B))
    want_stats = dataclasses.asdict(eng.stats)
    want_dag = eng.dag.as_dict()
    step_no = eng.checkpoint(tmp_path)

    del eng                       # "crash"
    eng2 = mk()
    got_step = eng2.restore(tmp_path)
    assert got_step == step_no

    eng3 = mk()                   # reference: what a fresh engine looks like
    with pytest.raises(AssertionError):
        assert_trees_equal(eng2.st, eng3.st)   # restore actually changed it

    eng4 = mk()
    eng4.restore(tmp_path, step=step_no)
    assert_trees_equal(eng2.st, eng4.st)       # deterministic restore

    assert dataclasses.asdict(eng2.stats) == want_stats
    assert eng2.dag.as_dict() == want_dag
    assert eng2.ckpt_max == int(eng2.st.mv.now)
    assert current_sig(eng2, range(B)) == want_view
    # the pinned lane's announce rides in the pytree: the pinned view
    # resolves identically post-restore
    tbl, ln = eng2.view_at(lane_ts)
    assert np.asarray(ln).sum() > 0
    ok, leaked, premature = forking.check_no_leak(eng2.st)
    assert ok, (leaked, premature)


def test_restore_missing_manifest_raises(tmp_path):
    eng = mk()
    with pytest.raises(FileNotFoundError):
        eng.restore(tmp_path / "nowhere")


def test_restore_then_reclaim_frees_checkpointed_only(tmp_path):
    """The tentpole safety/liveness pair, through a crash: after restore,
    a forced reclaim evicts idle-since-checkpoint sole survivors
    (ckpt_freed > 0) while active sequences — whose current versions moved
    past ckpt_max — keep every byte; the identical run without a checkpoint
    frees none of those pages."""
    eng = mk()
    warmup(eng)
    eng.checkpoint(tmp_path)
    del eng                                    # crash after the save

    eng = mk()
    eng.restore(tmp_path)
    assert eng.ckpt_max >= 0
    active = np.zeros((B,), bool)
    active[IDLE:] = True
    live_before = int(eng.space()["live_pages"])
    step(eng, active, 100)                     # active seqs pass ckpt_max
    want_active = current_sig(eng, range(IDLE, B))

    # the watermark crossing inside step() may already have fired the
    # eviction; the explicit reclaim makes it deterministic either way
    eng.reclaim(B * V)
    assert eng.stats.ckpt_evictions >= IDLE
    assert eng.stats.ckpt_freed > 0
    assert int(eng.space()["live_pages"]) < live_before
    # idle sole survivors are gone from the version store...
    tbl, ln = eng.view_at(2**31 - 2)
    assert np.asarray(ln)[:IDLE].sum() == 0
    # ...but every active byte survived the eviction
    assert current_sig(eng, range(IDLE, B)) == want_active
    ok, leaked, premature = forking.check_no_leak(eng.st)
    assert ok, (leaked, premature)

    # control: the same workload with no checkpoint cannot free those pages
    ctl = mk()
    warmup(ctl)
    step(ctl, active, 100)
    ctl.reclaim(B * V)
    assert ctl.stats.ckpt_freed == 0
    assert ctl.stats.ckpt_evictions == 0
    tbl, ln = ctl.view_at(2**31 - 2)
    assert np.asarray(ln)[:IDLE].sum() > 0     # idle current versions pinned
    assert int(ctl.space()["live_pages"]) > int(eng.space()["live_pages"])


def test_evicted_sequences_restorable_from_checkpoint(tmp_path):
    """Eviction is safe *because* restore can always bring the data back:
    after evicting the idle sole survivors, restoring the same checkpoint
    reproduces their pre-eviction bytes exactly."""
    eng = mk()
    warmup(eng)
    want_idle = current_sig(eng, range(IDLE))
    eng.checkpoint(tmp_path)
    active = np.zeros((B,), bool)
    active[IDLE:] = True
    step(eng, active, 100)
    eng.reclaim(B * V)
    assert eng.stats.ckpt_freed > 0
    tbl, ln = eng.view_at(2**31 - 2)
    assert np.asarray(ln)[:IDLE].sum() == 0    # idle views really gone

    eng.restore(tmp_path)
    assert current_sig(eng, range(IDLE)) == want_idle


def test_sharded_engine_checkpoint_roundtrip(tmp_path):
    """The host-sharded engine round-trips its vmapped state + host GC
    counters through the same manager format."""
    from repro.dist.mvgc import ShardedPagedKVEngine

    eng = ShardedPagedKVEngine(
        hosts=2, num_seqs=4, num_pages=12, page_size=4, max_pages_per_seq=3,
        kv_heads=KV_HEADS, head_dim=HEAD_DIM,
        gc=GCConfig(policy="slrt", versions_per_slot=6, reader_lanes=2,
                    hot_k=4))
    rng = np.random.default_rng(0)
    for i in range(5):
        kv = jnp.asarray(rng.standard_normal(
            (2, 4, KV_HEADS, HEAD_DIM)).astype(np.float32))
        eng.step(jnp.broadcast_to(jnp.arange(4, dtype=jnp.int32), (2, 4)),
                 kv, kv, jnp.ones((2, 4), bool))
    step_no = eng.checkpoint(tmp_path)
    want_forks = eng.forks
    del eng

    eng2 = ShardedPagedKVEngine(
        hosts=2, num_seqs=4, num_pages=12, page_size=4, max_pages_per_seq=3,
        kv_heads=KV_HEADS, head_dim=HEAD_DIM,
        gc=GCConfig(policy="slrt", versions_per_slot=6, reader_lanes=2,
                    hot_k=4))
    assert eng2.restore(tmp_path) == step_no
    assert eng2.forks == want_forks
    assert eng2.ckpt_max == int(jnp.min(eng2.st.mv.now))


def test_restore_keeps_the_pages_freed_but_not_drained(tmp_path):
    """Pages freed before a checkpoint and not yet drained by
    `freed_pages()` come back with the restored engine: both drains name
    the same free pages."""
    eng = mk()
    warmup(eng)
    eng.reset(jnp.arange(B, dtype=jnp.int32), jnp.ones((B,), bool))
    eng.reclaim(B * V)
    eng.checkpoint(tmp_path)
    eng2 = mk()
    eng2.restore(tmp_path)
    want = eng.freed_pages()
    assert want and eng2.freed_pages() == want
    assert all(np.asarray(eng2.st.free)[want])
    assert eng2.freed_pages() == []
