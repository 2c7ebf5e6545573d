"""The paged engines hand their state to the programs that return it:
those programs alias the whole state (the page pool is updated in place,
not copied), the programs that only read it alias nothing, and a state
handed over is gone while pinned snapshots read as before.  The sharded
engine runs the same programs on every shard, and donates as the
single-host engine does."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

jax.config.update("jax_platform_name", "cpu")

from repro.core.telemetry import GCConfig
from repro.dist.mvgc import ShardedPagedKVEngine
from repro.serve import engine as eng

N = 4
#: the sharded engine's host count
H = 3


def _engine(kind="single"):
    # 4 sequences, 8 pages of 2 tokens, 2 versions per slab: a few appends
    # overflow the slabs, so reclaim passes run
    gc = GCConfig(policy="slrt", versions_per_slot=2, reader_lanes=2)
    if kind == "sharded":
        return ShardedPagedKVEngine(H, N, 8, 2, 4, 1, 4, gc=gc)
    return eng.PagedKVEngine(N, 8, 2, 4, 1, 4, gc=gc)


def _hosts(e, x):
    """``x`` as engine ``e`` takes it: on every host for the sharded one."""
    if isinstance(e, ShardedPagedKVEngine):
        return jnp.broadcast_to(x[None], (H,) + x.shape)
    return x


def _ids():
    return jnp.arange(N, dtype=jnp.int32)


def _kv(step):
    return jnp.full((N, 1, 4), float(step), jnp.float32)


def _mask(*lanes):
    return jnp.asarray(np.isin(np.arange(N), lanes))


def _pool_bytes(e):
    return e.st.k_pages.nbytes + e.st.v_pages.nbytes


def _alias_bytes(fn, *args):
    return fn.lower(*args).compile().memory_analysis().alias_size_in_bytes


def _donating(e):
    ids, kv, mask = (_hosts(e, x) for x in (_ids(), _kv(0), _mask(0)))
    pins = e._pins()
    return {
        "_append": (e._append, (e.st, e._freed, e._first, ids, kv, kv, mask,
                                pins)),
        "_fork": (e._fork, (e.st, e._freed, e._first, ids, ids, mask,
                            pins)),
        "_reset": (e._reset, (e.st, e._freed, e._first, ids, mask, pins)),
        "_reclaim": (e._reclaim, (e.st, e._freed, e._first,
                                 np.int32(1), pins)),
        "_evict": (e._evict, (e.st, e._freed, np.int32(0), pins)),
    }


def _reading(e):
    return {
        "_live": (e._live, (e.st,)),
        "_read": (e._read, (e.st._replace(k_pages=None, v_pages=None),
                            _ids(), np.int32(0))),
    }


@pytest.mark.parametrize("kind", ["single", "sharded"])
@pytest.mark.parametrize("name", ["_append", "_fork", "_reset", "_reclaim",
                                  "_evict"])
def test_state_returning_programs_alias_the_pool(name, kind):
    """The pool and the freed-since-drain bits (the sharded engine keeps
    none) are both updated in place."""
    e = _engine(kind)
    fn, args = _donating(e)[name]
    bits = sum(x.nbytes for x in jax.tree.leaves(e._freed))
    assert _alias_bytes(fn, *args) >= _pool_bytes(e) + bits


@pytest.mark.parametrize("name", ["_live", "_read"])
def test_reading_programs_alias_nothing(name):
    e = _engine()
    fn, args = _reading(e)[name]
    assert _alias_bytes(fn, *args) == 0


@pytest.mark.parametrize("kind", ["single", "sharded"])
def test_step_consumes_the_state_it_was_given(kind):
    e = _engine(kind)
    before, freed = e.st, jax.tree.leaves(e._freed)
    e.step(*(_hosts(e, x) for x in (_ids(), _kv(1), _kv(1),
                                    _mask(0, 1, 2, 3))))
    assert before.k_pages.is_deleted() and before.v_pages.is_deleted()
    assert all(x.is_deleted() for x in freed)
    assert not any(x.is_deleted() for x in jax.tree.leaves(e._freed))
    assert not e.st.k_pages.is_deleted()
    # the new pool holds the four tokens appended, head_dim 4 each, on
    # every host
    hosts = H if kind == "sharded" else 1
    assert float(jnp.abs(e.st.k_pages).sum()) == 16.0 * hosts


def test_reset_and_reclaim_consume_the_state():
    e = _engine()
    e.step(_ids(), _kv(1), _kv(1), _mask(0, 1))
    before = e.st
    e.reset(_ids(), _mask(0))
    assert before.k_pages.is_deleted()
    before = e.st
    e.reclaim(deficit=8)
    assert before.k_pages.is_deleted()


def test_pinned_view_survives_reclaim_and_reset():
    e = _engine()
    ids = _ids()
    for s in range(3):
        e.step(ids, _kv(s + 1), _kv(s + 1), _mask(0, 1, 2, 3))
    t = e.pin(0)
    tables, lengths = map(np.asarray, e.view_at(t))
    assert lengths.tolist() == [3, 3, 3, 3]
    e.reclaim(deficit=8)
    e.reset(ids, _mask(0, 2))
    e.step(ids, _kv(9), _kv(9), _mask(1, 3))
    after_t, after_l = map(np.asarray, e.view_at(t))
    np.testing.assert_array_equal(after_t, tables)
    np.testing.assert_array_equal(after_l, lengths)
    # the pages the pinned view names still hold the tokens appended
    k = np.asarray(e.st.k_pages)
    for seq in range(N):
        rows = [k[tables[seq, i // 2], i % 2, 0, 0] for i in range(3)]
        assert rows == [1.0, 2.0, 3.0]
    e.unpin(0)
