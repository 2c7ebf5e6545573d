"""The serving engines' own measurement: the spans they write, the host
syncs they count, the names their programs run under, and the programs
that must not copy the page pool or the weights."""
import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest

jax.config.update("jax_platform_name", "cpu")

from repro.configs import reduced_config
from repro.configs.base import SHAPES, RunConfig
from repro.core import telemetry
from repro.core.telemetry import GCConfig
from repro.models import transformer as tf
from repro.serve import engine as eng


@pytest.fixture
def spans(monkeypatch):
    """Every span the engines open, as (name, args), in order."""
    seen = []
    real = telemetry.span

    def record(name, **args):
        seen.append((name, args))
        return real(name, **args)

    monkeypatch.setattr(telemetry, "span", record)
    monkeypatch.setattr(eng, "span", record)
    return seen


def _paged(num_pages=8, versions=2, hosts=None):
    # 4 sequences, pages of 2 tokens, 2 versions per slab: appends soon
    # overflow the slabs and the pool, so reclaim passes run; with
    # ``hosts``, the sharded engine with that many
    gc = GCConfig(policy="slrt", versions_per_slot=versions, reader_lanes=2)
    if hosts:
        from repro.dist.mvgc import ShardedPagedKVEngine
        return ShardedPagedKVEngine(hosts, 4, num_pages, 2, 4, 1, 4, gc=gc)
    return eng.PagedKVEngine(4, num_pages, 2, 4, 1, 4, gc=gc)


def _serve():
    cfg = reduced_config("minitron-4b")
    run = RunConfig(model=cfg, shape=SHAPES["decode_32k"], gc_policy="slrt",
                    versions_per_slot=8, reader_lanes=4)
    params = tf.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, eng.MVServeEngine(cfg, run, params, batch=4, max_len=32)


def _counts(seen):
    return collections.Counter(name for name, _ in seen)


def test_paged_spans_and_syncs_under_reclaim(spans):
    e = _paged()
    ids = jnp.arange(4, dtype=jnp.int32)
    kv = jnp.ones((4, 1, 4), jnp.float32)
    on = jnp.ones((4,), bool)
    t = e.pin(0)
    for _ in range(5):
        e.step(ids, kv, kv, on)
    e.view_at(t)
    e.unpin(0)
    e.reset(ids, on)
    got = _counts(spans)
    assert e.stats.reclaims_triggered > 0
    assert got["repro.gc.reclaim"] == e.stats.reclaims_triggered
    assert got["repro.engine.sync"] == e.counters.host_syncs > 0
    assert got["repro.engine.step"] == e.counters.steps == 5
    assert [a["step"] for n, a in spans if n == "repro.engine.step"] == \
        list(range(5))
    # one append span per run of the append program, retries included
    assert got["repro.pool.append"] >= 5
    assert (got["repro.engine.reset"], got["repro.snapshot.pin"],
            got["repro.snapshot.read"], got["repro.snapshot.unpin"]) == \
        (1, 1, 1, 1)
    assert e.space()["host_syncs"] == e.counters.host_syncs


def _count_programs(e):
    """Wrap the engine's pool and GC programs so that each call is counted
    by name; returns the counter."""
    runs = collections.Counter()
    for attr in ("_append", "_reset", "_fork", "_live", "_reclaim",
                 "_evict"):
        prog = getattr(e, attr)

        def counted(*args, _prog=prog, _name=attr):
            runs[_name] += 1
            return _prog(*args)

        setattr(e, attr, counted)
    return runs


@pytest.mark.parametrize("hosts", [None, 3], ids=["single", "sharded"])
def test_paged_step_reads_failed_once_per_round(spans, hosts):
    """With no reclaim and no watermark crossed, a step runs one program,
    the append, and reads its failed lanes, live count and gate in one
    fetch: one sync.  The sharded engine runs the same loop, and reads
    once more before the append, for the global LWM it passes every
    shard."""
    e = _paged(num_pages=64, versions=8, hosts=hosts)
    runs = _count_programs(e)
    ids = jnp.arange(4, dtype=jnp.int32)
    kv = jnp.ones((4, 1, 4), jnp.float32)
    on = jnp.ones((4,), bool)
    if hosts:
        ids, kv, on = (jnp.broadcast_to(x[None], (hosts,) + x.shape)
                       for x in (ids, kv, on))
    failed = e.step(ids, kv, kv, on)
    assert isinstance(failed, np.ndarray) and not failed.any()
    assert failed.shape == on.shape
    assert e.stats.reclaims_triggered == 0
    assert e.counters.host_syncs == (2 if hosts else 1)
    assert runs == {"_append": 1}
    assert e.stats.peak_live == 4 * (hosts or 1)


def test_paged_reclaim_step_reads_once_per_round(spans):
    """A step that reclaims reads once, plus once per reclaim pass: each
    retry round dispatches the reclaim and the retried append back to back
    and reads both with one fetch; a watermark pass adds its program and
    its read."""
    e = _paged()
    runs = _count_programs(e)
    ids = jnp.arange(4, dtype=jnp.int32)
    kv = jnp.ones((4, 1, 4), jnp.float32)
    on = jnp.ones((4,), bool)
    seen = 0
    for _ in range(12):
        syncs, passes = e.counters.host_syncs, e.stats.reclaims_triggered
        runs.clear()
        e.step(ids, kv, kv, on)
        passes = e.stats.reclaims_triggered - passes
        if not passes:
            continue
        seen += 1
        assert e.counters.host_syncs - syncs == 1 + passes
        assert runs["_reclaim"] == passes
        # the first append, and one retry behind each pass but a
        # watermark pass, which ends the step
        assert passes <= runs["_append"] <= 1 + passes
        assert set(runs) <= {"_append", "_reclaim"}
    assert seen and e.stats.give_ups > 0


def test_serve_spans_and_syncs(spans):
    cfg, e = _serve()
    e.prefill(jnp.ones((4, 6), jnp.int32))
    for _ in range(3):
        e.step()
    t = e.pin(1)
    e.lengths_at(t)
    e.unpin(1)
    got = _counts(spans)
    assert got["repro.engine.sync"] == e.counters.host_syncs == 3 + 1
    assert got["repro.engine.step"] == e.counters.steps == 3
    assert got["repro.engine.prefill"] == 1
    assert (got["repro.snapshot.pin"], got["repro.snapshot.read"],
            got["repro.snapshot.unpin"]) == (1, 1, 1)


def test_serve_last_stats_holds_the_read_monitors():
    _, e = _serve()
    e.prefill(jnp.ones((4, 6), jnp.int32))
    e.step()
    assert set(e.last_stats) == {"retry_failed", "overflow_count",
                                 "dropped_retires"}
    assert all(isinstance(v, int) for v in e.last_stats.values())


def _paged_programs(e):
    ids = jnp.arange(4, dtype=jnp.int32)
    kv = jnp.ones((4, 1, 4), jnp.float32)
    on = jnp.ones((4,), bool)
    st, i32 = e.st, np.int32(1)
    return {
        "jit_pool_append": (e._append, (st, e._freed, e._first, ids, kv,
                                        kv, on)),
        "jit_pool_reset": (e._reset, (st, e._freed, e._first, ids, on)),
        "jit_pool_fork": (e._fork, (st, e._freed, e._first, ids, ids[::-1],
                                    on)),
        "jit_pool_live": (e._live, (st,)),
        "jit_gc_reclaim": (e._reclaim, (st, e._freed, e._first, i32)),
        "jit_gc_evict": (e._evict, (st, e._freed, i32)),
        "jit_snapshot_read": (e._read, (st._replace(k_pages=None,
                                                    v_pages=None), ids, i32)),
        "jit_snapshot_pin": (eng._pin, (st.mv, i32)),
        "jit_snapshot_unpin": (eng._unpin, (st.mv, i32)),
    }


def test_paged_programs_run_under_their_names():
    for name, (prog, args) in _paged_programs(_paged()).items():
        assert f"module @{name} " in prog.lower(*args).as_text(), name


def test_serve_programs_run_under_their_names():
    _, e = _serve()
    bare = e.state._replace(params=None, cache=None)
    for name, (prog, args) in {
            "jit_decode": (e._decode, (e.state,)),
            "jit_prefill": (e._prefill, (e.state,
                                         jnp.ones((4, 6), jnp.int32))),
            "jit_snapshot_read": (e._read, (bare, np.int32(0))),
            "jit_snapshot_pin": (eng._pin, (e.state.mv, np.int32(0))),
            "jit_snapshot_unpin": (eng._unpin, (e.state.mv, np.int32(0))),
    }.items():
        assert f"module @{name} " in prog.lower(*args).as_text(), name


def _field_names(tree):
    return {getattr(k, "name", getattr(k, "key", None))
            for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]
            for k in path}


@pytest.mark.parametrize("which", ["paged", "serve"])
def test_snapshot_programs_return_no_pool_and_no_weights(which):
    if which == "paged":
        e = _paged()
        mv, big = e.st.mv, e.st.k_pages
        read = jax.eval_shape(e._read, e.st._replace(k_pages=None,
                                                     v_pages=None),
                              jnp.arange(4, dtype=jnp.int32), np.int32(0))
    else:
        _, e = _serve()
        mv, big = e.state.mv, max(jax.tree.leaves(e.state.params),
                                  key=lambda x: x.size)
        read = jax.eval_shape(e._read, e.state._replace(params=None,
                                                        cache=None),
                              np.int32(0))
    for out in (jax.eval_shape(eng._pin, mv, np.int32(0)),
                jax.eval_shape(eng._unpin, mv, np.int32(0)), read):
        assert not _field_names(out) & {"k_pages", "v_pages", "params",
                                        "cache"}
        assert all(x.size < big.size for x in jax.tree.leaves(out))

