"""`PagedKVEngine`'s host loop reads each round with one fetch: the pool
programs return the failed lanes, the live count and the watermark gate,
the reclaim pass computes its deficit and hot set in-graph, and freed pages
collect in a device bitmap that `freed_pages()` drains.  The decisions must
be those of the plain loop that reads every value as it needs it.  That
loop is written out here from `mvkv.paged` calls alone, and both are driven
through the same random trace: appends that overflow the slabs and the
pool, watermark crossings, resets, forks, pins held across reclaims,
explicit reclaims, an armed checkpoint's evictions and lanes that give up
after every reclaim round."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

jax.config.update("jax_platform_name", "cpu")

from paged_trace import B, D, HKV, MP, PAGES, PS
from paged_trace import trace as _trace
from repro.core.telemetry import GCConfig, ReclaimStats
from repro.mvkv import paged
from repro.serve import engine as eng


class Oracle:
    """The loop that reads every value when it needs it: the free bitmap
    before and after each op, the failed lanes, the live count, the gate,
    the hot set, the pages each reclaim freed."""

    def __init__(self, gc: GCConfig):
        self.gc = gc
        self.st = paged.make_paged_kv(B, PAGES, PS, MP, HKV, D, gc=gc,
                                      dtype=jnp.float32)
        self.stats = ReclaimStats(unit="pages")
        self.freed = []
        self.ckpt_max = -1
        self.watermark_passes = 0
        self.retry_passes = 0
        pol = gc.policy
        self._append = jax.jit(lambda st, i, k, v, m: paged.append_tokens(
            st, i, k, v, m, gc_policy=pol))
        self._reset = jax.jit(lambda st, i, m: paged.reset_sequence(
            st, i, m, gc_policy=pol))
        self._fork = jax.jit(lambda st, s, d, m: paged.fork_sequence(
            st, s, d, m, gc_policy=pol))
        self._reclaim = jax.jit(lambda st, h, d: paged.reclaim_on_pressure(
            st, h, d, gc_policy=pol))
        self._evict = jax.jit(paged.evict_checkpointed)

    def _gate(self):
        return paged.page_pressure(self.st, watermark=self.gc.page_watermark)

    def _live(self) -> int:
        return int(paged.live_pages(self.st))

    def _reclaim_once(self, extra: int) -> int:
        deficit = max(int(self._gate().deficit), extra, 1)
        self.st, pages = self._reclaim(
            self.st, paged.hot_sequences(self.st, k=self.gc.hot_k),
            jnp.int32(deficit))
        freed = int(pages)
        if self.ckpt_max >= 0 and bool(self._gate().under_pressure):
            self.st, ck, n_ev = self._evict(self.st, jnp.int32(self.ckpt_max))
            self.stats.note_ckpt_eviction(int(n_ev), int(ck))
            freed += int(ck)
        self.stats.note_reclaim(freed, self._live())
        return freed

    def _retrying(self, op, *args, mask, peak=True) -> np.ndarray:
        rounds = 0
        while True:
            self.st, mask = op(self.st, *args, mask)
            failed = np.asarray(mask)
            if peak:
                self.stats.note_live(self._live())
            if not failed.any() or rounds >= self.gc.max_reclaim_rounds:
                break
            self.stats.note_event()
            self.retry_passes += 1
            self._reclaim_once(int(failed.sum()))
            rounds += 1
        self.stats.give_ups += int(failed.sum())
        return failed

    def _tracking_freed(self, body):
        before = np.asarray(self.st.free)
        out = body()
        self.freed += np.flatnonzero(np.asarray(self.st.free)
                                     & ~before).tolist()
        return out

    def step(self, ids, k, v, mask):
        def body():
            failed = self._retrying(self._append, ids, k, v, mask=mask)
            if bool(self._gate().under_pressure):
                self.stats.note_event()
                self.watermark_passes += 1
                self._reclaim_once(0)
            return failed
        return self._tracking_freed(body)

    def reset(self, ids, mask):
        return self._tracking_freed(lambda: self._retrying(
            self._reset, ids, mask=mask, peak=False))

    def fork(self, src, dst, mask):
        return self._tracking_freed(lambda: self._retrying(
            self._fork, src, dst, mask=mask))

    def reclaim(self, deficit):
        def body():
            before = self._live()
            self.stats.note_event()
            self._reclaim_once(0 if deficit is None else deficit)
            return before - self._live()
        return self._tracking_freed(body)

    def pin(self, lane):
        self.st, ts = paged.begin_snapshot(self.st, jnp.int32(lane))
        return int(ts)

    def unpin(self, lane):
        self.st = paged.end_snapshot(self.st, jnp.int32(lane))

    def freed_pages(self):
        out, self.freed = self.freed, []
        return out


def _assert_same(e, o, got, want, what):
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                  err_msg=what)
    la, ta = jax.tree_util.tree_flatten(e.st)
    lb, tb = jax.tree_util.tree_flatten(o.st)
    assert ta == tb
    for a, b in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=what)
    assert dataclasses.asdict(e.stats) == dataclasses.asdict(o.stats), what
    free = np.asarray(e.st.free)
    drained = e.freed_pages()
    assert all(free[p] for p in drained), what
    assert set(drained) == set(o.freed_pages()), what


@pytest.mark.parametrize("policy", ["slrt", "ebr", "steam"])
def test_engine_matches_the_read_as_needed_loop(policy):
    gc = GCConfig(policy=policy, versions_per_slot=2, reader_lanes=2)
    e = eng.PagedKVEngine(B, PAGES, PS, MP, HKV, D, gc=gc)
    o = Oracle(gc)
    ids = jnp.arange(B, dtype=jnp.int32)
    rng = np.random.default_rng(["slrt", "ebr", "steam"].index(policy))
    pinned = set()
    reclaims_under_pin = 0
    for n, (op, args) in enumerate(_trace(rng, 80)):
        what = f"{policy} op {n}: {op}{args}"
        held, passes = bool(pinned), o.stats.reclaims_triggered
        if op == "step":
            mask, val = jnp.asarray(args[0]), args[1]
            kv = jnp.full((B, HKV, D), val, jnp.float32) + ids[:, None, None]
            got, want = e.step(ids, kv, kv, mask), o.step(ids, kv, kv, mask)
        elif op == "reset":
            mask = jnp.asarray(args[0])
            got, want = e.reset(ids, mask), o.reset(ids, mask)
        elif op == "fork":
            src, dst = (jnp.asarray([x], jnp.int32) for x in args)
            one = jnp.ones((1,), bool)
            got, want = e.fork(src, dst, one), o.fork(src, dst, one)
        elif op == "pin":
            if args[0] in pinned:
                continue
            pinned.add(args[0])
            got, want = e.pin(args[0]), o.pin(args[0])
        elif op == "unpin":
            if args[0] not in pinned:
                continue
            pinned.discard(args[0])
            e.unpin(args[0])
            o.unpin(args[0])
            got = want = 0
        elif op == "reclaim":
            got, want = e.reclaim(args[0]), o.reclaim(args[0])
        else:
            # arms the checkpoint-eviction post-pass as `checkpoint` does,
            # without writing one
            e.ckpt_max = o.ckpt_max = int(o.st.mv.now)
            got = want = 0
        reclaims_under_pin += held and o.stats.reclaims_triggered > passes
        _assert_same(e, o, got, want, what)
    # the trace reached every path the loop has
    assert o.retry_passes and o.watermark_passes and o.stats.give_ups
    assert e.forks and reclaims_under_pin and e.ckpt_max >= 0
