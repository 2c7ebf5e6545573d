"""MV-Serve: the multiversioned serving engine.

The paper's workload shape — frequent updates + long read-only transactions —
maps onto serving as:

* **updates**: every decode step advances each sequence's *cache descriptor*
  (a versioned CAS object holding the visible cache length; with the paged
  backend, the page table).  One version per step, timestamped by the global
  decode clock — `vstore.write_step`.
* **rtxs**: scoring passes, speculative-branch evaluation, and prefix-cache
  lookups pin a timestamp (`begin_snapshot`) and read a *consistent
  cross-sequence snapshot* of descriptors (`snapshot_read` = the paper's
  ``search(t)``), attending only over each sequence's prefix as of the pinned
  step — while decode keeps writing.
* **MVGC**: obsolete descriptor versions are reclaimed by the configured
  policy (SL-RT by default); Theorem 1's bound means descriptor space is
  O(pinned snapshots + lanes log lanes), never O(steps).

The descriptor store is tiny next to the KV pages it governs — but it is what
*pins pages*: a page can be recycled only when no reachable descriptor
version references it.  `freed_pages()` exposes exactly the handles whose
last referencing version was collected, closing the loop to the page
allocator.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Any, Dict, List, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.ckpt.manager import CheckpointManager
from repro.configs.base import ModelConfig, RunConfig
from repro.core.mvgc import vstore
from repro.core.telemetry import (EngineCounters, GCConfig, ReclaimStats,
                                  fetch, resolve_gc_config, span)
from repro.models import transformer as tf
from repro.mvkv import paged
from repro.serve.forking import ForkDAG


class ServeState(NamedTuple):
    params: Any
    cache: Any
    cache_len: jax.Array      # i32[B]
    mv: vstore.MVState        # versioned cache descriptors (1 slot / sequence)
    last_tokens: jax.Array    # i32[B, 1]


def make_serve_state(cfg: ModelConfig, run: RunConfig, params, batch: int,
                     max_len: int, dtype=jnp.bfloat16) -> ServeState:
    cache = tf.init_cache(cfg, batch, max_len, dtype)
    gc = run.gc
    mv = vstore.make_state(
        num_slots=batch,
        versions_per_slot=gc.versions_per_slot,
        num_reader_lanes=gc.reader_lanes,
        ring_capacity=gc.ring_capacity or max(16, batch * 2),
    )
    return ServeState(
        params=params,
        cache=cache,
        cache_len=jnp.zeros((batch,), jnp.int32),
        mv=mv,
        last_tokens=jnp.zeros((batch, 1), jnp.int32),
    )


# ---------------------------------------------------------------------------
# core steps (pure; jit these)
# ---------------------------------------------------------------------------
def prefill_step(state: ServeState, cfg: ModelConfig, run: RunConfig,
                 tokens: jax.Array,
                 frontend_embeds: Optional[jax.Array] = None) -> ServeState:
    logits, cache, lens = tf.prefill(state.params, cfg, tokens, state.cache,
                                     frontend_embeds=frontend_embeds)
    B = tokens.shape[0]
    ids = jnp.arange(B, dtype=jnp.int32)
    mv, _, _ = vstore.write_step(
        state.mv, ids, lens, jnp.ones((B,), bool), policy=run.gc.policy,
        **run.gc.kernel_kwargs())
    nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
    return ServeState(state.params, cache, lens, mv, nxt)


def decode_one(state: ServeState, cfg: ModelConfig, run: RunConfig,
               enc_out: Optional[jax.Array] = None
               ) -> Tuple[ServeState, jax.Array, jax.Array, Dict[str, jax.Array]]:
    """One greedy decode step for the whole batch.  Returns
    (state', new_tokens[B,1], freed_descriptor_payloads, stats).

    GC runs trigger-on-event (DESIGN.md §11): after the descriptor write the
    capacity gate decides — under pressure (a watermark crossed, or any lane's
    append overflowed its slab) the step reclaims *synchronously* via
    `vstore.reclaim_on_pressure` and retries the overflowed lanes in-graph;
    otherwise the policy's normal cadence pass runs.  ``stats`` holds the
    i32 monitors an operator reads: ``retry_failed`` (lanes whose retried
    descriptor write still overflowed), and the store's running
    ``overflow_count`` and ``dropped_retires``."""
    logits, cache = tf.decode_step(state.params, cfg, state.last_tokens,
                                   state.cache, state.cache_len,
                                   enc_out=enc_out)
    new_len = state.cache_len + 1
    B = new_len.shape[0]
    ids = jnp.arange(B, dtype=jnp.int32)
    # the update: a new descriptor version (visible length) per sequence
    mv, freed_w, ovf = vstore.write_step(
        state.mv, ids, new_len, jnp.ones((B,), bool), policy=run.gc.policy,
        **run.gc.kernel_kwargs())
    gate = vstore.capacity_gate(mv)
    trigger = gate.under_pressure | ovf.any()

    def _pressure(m: vstore.MVState):
        hs = vstore.hot_slots(m, min(8, B))
        return vstore.reclaim_on_pressure(
            m, hs, gate.deficit, policy=run.gc.policy,
            **run.gc.kernel_kwargs())[0]

    def _cadence(m: vstore.MVState):
        return vstore.gc_step(m, policy=run.gc.policy,
                              **run.gc.kernel_kwargs())[0]

    mv = jax.lax.cond(trigger, _pressure, _cadence, mv)

    # retry the overflowed lanes now that the reclaim made room
    def _retry(args):
        m, o = args
        m2, _, o2 = vstore.write_step(
            m, ids, new_len, o, policy=run.gc.policy,
            **run.gc.kernel_kwargs())
        return m2, o2

    mv, ovf_left = jax.lax.cond(
        ovf.any(), _retry, lambda args: args, (mv, ovf))

    stats = {
        "retry_failed": ovf_left.sum().astype(jnp.int32),
        "overflow_count": mv.overflow_count,
        "dropped_retires": mv.dropped_retires,
    }
    nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
    return (ServeState(state.params, cache, new_len, mv, nxt), nxt,
            freed_w.reshape(-1), stats)


# ---------------------------------------------------------------------------
# snapshot (rtx) interface
# ---------------------------------------------------------------------------
# the engines' pin and unpin programs (``jit_snapshot_pin``/``_unpin``)
_pin = jax.jit(paged.snapshot_pin)
_unpin = jax.jit(paged.snapshot_unpin)


def snapshot_lengths(state: ServeState, t: jax.Array,
                     seq_ids: Optional[jax.Array] = None,
                     use_kernel: Optional[bool] = None,
                     interpret: Optional[bool] = None,
                     ) -> Tuple[jax.Array, jax.Array]:
    """Consistent cross-sequence snapshot: each sequence's visible length as
    of pinned time t (the paper's rtx over many vCAS objects)."""
    if seq_ids is None:
        seq_ids = jnp.arange(state.cache_len.shape[0], dtype=jnp.int32)
    return vstore.snapshot_read(state.mv, seq_ids, t,
                                use_kernel=use_kernel, interpret=interpret)


def snapshot_score(state: ServeState, cfg: ModelConfig, tokens: jax.Array,
                   t: jax.Array) -> jax.Array:
    """Score candidate tokens against the snapshot at t: attention masks use
    the snapshot lengths, so the result is atomic w.r.t. ongoing decodes."""
    lens, found = snapshot_lengths(state, t)
    lens = jnp.where(found, lens, 0)
    logits, _ = tf.decode_step(state.params, cfg, tokens, state.cache, lens)
    return logits


# ---------------------------------------------------------------------------
# host-side engine wrapper
# ---------------------------------------------------------------------------
class MVServeEngine:
    """Orchestrates jitted prefill/decode/GC with the MVGC policy, and
    exposes the space report the benchmarks track.

    Each call writes a ``repro.engine.*`` or ``repro.snapshot.*`` span (see
    `repro.core.telemetry.span`), its programs run as ``jit_prefill``,
    ``jit_decode``, ``jit_snapshot_pin``, ``jit_snapshot_unpin`` and
    ``jit_snapshot_read``, and ``counters`` counts steps and host syncs."""

    def __init__(self, cfg: ModelConfig, run: RunConfig, params, batch: int,
                 max_len: int, dtype=jnp.float32):
        self.cfg, self.run = cfg, run
        self.state = make_serve_state(cfg, run, params, batch, max_len, dtype)
        # the jitted steps return the state without its parameters: they
        # pass through unchanged, and a jitted program would write a fresh
        # copy of every weight on each call
        def decode(state):
            new, toks, freed, stats = decode_one(state, cfg, run)
            return new._replace(params=None), toks, freed, stats

        def prefill(state, tokens):
            return prefill_step(state, cfg, run, tokens)._replace(params=None)

        kern = run.gc.kernel_kwargs()

        def snapshot_read(state, t):
            lens, found = snapshot_lengths(state, t, **kern)
            return jnp.where(found, lens, 0)

        self._decode = jax.jit(decode)
        self._prefill = jax.jit(prefill)
        self._read = jax.jit(snapshot_read)
        self.counters = EngineCounters()
        self.last_stats: Dict[str, int] = {}

    def prefill(self, tokens: jax.Array) -> None:
        with span("repro.engine.prefill"):
            new = self._prefill(self.state, tokens)
            self.state = new._replace(params=self.state.params)

    def step(self) -> jax.Array:
        with span("repro.engine.step", step=self.counters.steps):
            new, toks, _, stats = self._decode(self.state)
            self.state = new._replace(params=self.state.params)
            self.last_stats = {k: int(v) for k, v in
                               fetch(stats, self.counters).items()}
            self.counters.steps += 1
        return toks

    def compile_decode(self) -> jax.stages.Compiled:
        """Compile the decode step for the current state ahead of the first
        `step`, which then reuses the program; its ``as_text()`` shows which
        kernels the step runs."""
        return self._decode.lower(self.state).compile()

    def pin(self, lane: int) -> int:
        with span("repro.snapshot.pin"):
            mv, ts = _pin(self.state.mv, np.int32(lane))
            self.state = self.state._replace(mv=mv)
            return int(fetch(ts, self.counters))

    def unpin(self, lane: int) -> None:
        with span("repro.snapshot.unpin"):
            self.state = self.state._replace(
                mv=_unpin(self.state.mv, np.int32(lane)))

    def lengths_at(self, t: int) -> jax.Array:
        """Every sequence's visible length at pinned time ``t`` (0 where
        it had none)."""
        with span("repro.snapshot.read"):
            # the read takes the descriptors and lengths, not the weights
            # or the cache
            return self._read(self.state._replace(params=None, cache=None),
                              np.int32(t))

    def space(self) -> Dict[str, int]:
        return {**vstore.space_report(self.state.mv),
                **self.counters.as_row()}


class PagedKVEngine:
    """Paged-KV serving loop with synchronous pressure reclamation — the
    `freed_pages()` contract the module docstring promises, made concrete.

    ``step`` appends one token per masked sequence.  A failed append (page
    pool, table pool, or descriptor slab exhausted) is a **pressure event**:
    the engine reclaims synchronously — hot-sequence-first descriptor
    compaction, then the reachability sweep that recycles pages — and retries
    the failed lanes, up to ``max_reclaim_rounds`` before giving up (turso's
    trigger-on-event rule; the sim's abort => reclaim => retry loop).  A
    post-step watermark crossing triggers the same pass without a failure.
    Accounting lives in one :class:`repro.core.telemetry.ReclaimStats`
    (``self.stats``); the schema-v4 counter names (``pressure_events``,
    ``reclaims_triggered``, ``pages_reclaimed``, ``peak_pages``,
    ``peak_pages_post_reclaim``, ``give_ups``) survive as read-only
    properties feeding BENCH_serve rows directly.

    Configuration lives in one :class:`repro.core.telemetry.GCConfig`
    (``gc=``); the old per-kwarg spellings (``versions_per_seq``,
    ``gc_policy``, ``page_watermark``, ...) still work for one release but
    emit ``DeprecationWarning`` (DESIGN.md §13).

    Every program it launches has a stable name (``jit_pool_*``,
    ``jit_gc_*``, ``jit_snapshot_*`` in a device trace), each call writes a
    ``repro.*`` span (`repro.core.telemetry.span`), and every device value
    it reads on the host goes through `repro.core.telemetry.fetch`, counted
    in ``counters.host_syncs``."""

    def __init__(self, num_seqs: int, num_pages: int, page_size: int,
                 max_pages_per_seq: int, kv_heads: int, head_dim: int, *,
                 gc: Optional[GCConfig] = None,
                 versions_per_seq: Optional[int] = None,
                 reader_lanes: Optional[int] = None,
                 ring_capacity: Optional[int] = None,
                 gc_policy: Optional[str] = None,
                 page_watermark: Optional[float] = None,
                 hot_k: Optional[int] = None,
                 max_reclaim_rounds: Optional[int] = None,
                 use_kernel: Optional[bool] = None,
                 kernel_interpret: Optional[bool] = None,
                 eager_fork: bool = False, dtype=jnp.float32):
        cfg = resolve_gc_config(
            gc, "PagedKVEngine",
            versions_per_slot=versions_per_seq, reader_lanes=reader_lanes,
            ring_capacity=ring_capacity, policy=gc_policy,
            page_watermark=page_watermark, hot_k=hot_k,
            max_reclaim_rounds=max_reclaim_rounds, use_kernel=use_kernel,
            kernel_interpret=kernel_interpret)
        self.gc = cfg
        self.st = paged.make_paged_kv(
            num_seqs, num_pages, page_size, max_pages_per_seq, kv_heads,
            head_dim, gc=cfg, dtype=dtype)
        self.gc_policy = cfg.policy
        self.max_reclaim_rounds = cfg.max_reclaim_rounds
        kern = cfg.kernel_kwargs()
        self.use_kernel = kern["use_kernel"]
        self.kernel_interpret = kern["interpret"]
        policy = self.gc_policy

        # one named function per program, so that a device trace shows
        # ``jit_<name>`` (a jitted ``partial`` runs as ``jit__unknown``)
        def pool_append(st, seq_ids, k_new, v_new, mask):
            return paged.append_tokens(st, seq_ids, k_new, v_new, mask,
                                       gc_policy=policy, **kern)

        def pool_fork(st, src_ids, dst_ids, mask):
            return paged.fork_sequence(st, src_ids, dst_ids, mask,
                                       gc_policy=policy,
                                       copy_pages=eager_fork, **kern)

        def pool_reset(st, seq_ids, mask):
            return paged.reset_sequence(st, seq_ids, mask, gc_policy=policy,
                                        **kern)

        def pool_live(st):
            return paged.live_pages(st)

        def gc_gate(st):
            return paged.page_pressure(st, watermark=cfg.page_watermark)

        def gc_hot(st):
            return paged.hot_sequences(st, k=cfg.hot_k)

        def gc_reclaim(st, hot_keys, deficit):
            return paged.reclaim_on_pressure(st, hot_keys, deficit,
                                             gc_policy=policy, **kern)

        def gc_evict(st, ckpt_max):
            return paged.evict_checkpointed(st, ckpt_max)

        def snapshot_read(st, seq_ids, t):
            return paged.snapshot_view(st, seq_ids, t, **kern)

        self._append = jax.jit(pool_append)
        self._fork = jax.jit(pool_fork)
        self._reset = jax.jit(pool_reset)
        self._live = jax.jit(pool_live)
        self._gate = jax.jit(gc_gate)
        self._hot = jax.jit(gc_hot)
        self._reclaim = jax.jit(gc_reclaim)
        self._evict = jax.jit(gc_evict)
        self._read = jax.jit(snapshot_read)
        self._all_seqs = jnp.arange(num_seqs, dtype=jnp.int32)
        self.counters = EngineCounters()
        self._freed_pages: List[int] = []
        self.stats = ReclaimStats(unit="pages")
        self.eager_fork = eager_fork
        self.dag = ForkDAG()
        #: highest durably checkpointed timestamp; -1 = no checkpoint taken.
        #: Setting it (via `checkpoint()`) arms the sole-survivor eviction
        #: rule in `_reclaim_once` (DESIGN.md §14).
        self.ckpt_max: int = -1

    # schema-v4 counter names, now backed by the unified ReclaimStats
    @property
    def pressure_events(self) -> int:
        return self.stats.pressure_events

    @property
    def reclaims_triggered(self) -> int:
        return self.stats.reclaims_triggered

    @property
    def pages_reclaimed(self) -> int:
        return self.stats.reclaimed

    @property
    def give_ups(self) -> int:
        return self.stats.give_ups

    @property
    def peak_pages(self) -> int:
        return self.stats.peak_live

    @property
    def peak_pages_post_reclaim(self) -> int:
        return self.stats.peak_live_post_reclaim

    @property
    def forks(self) -> int:
        return self.dag.forks

    @property
    def joins(self) -> int:
        return self.dag.joins

    @property
    def releases(self) -> int:
        return self.dag.releases

    def _fetch(self, tree):
        return fetch(tree, self.counters)

    def _live_pages(self) -> int:
        return int(self._fetch(self._live(self.st)))

    def _note_peak(self) -> None:
        self.stats.note_live(self._live_pages())

    def _note_freed(self, free_before: np.ndarray) -> None:
        newly = np.flatnonzero(self._fetch(self.st.free) & ~free_before)
        self._freed_pages.extend(int(p) for p in newly)

    def _reclaim_once(self, extra_deficit: int = 0) -> None:
        with span("repro.gc.reclaim"):
            gate = self._gate(self.st)
            deficit = max(int(self._fetch(gate.deficit)), extra_deficit, 1)
            self.st, pages = self._reclaim(self.st, self._hot(self.st),
                                           np.int32(deficit))
            freed = int(self._fetch(pages))
            # Checkpoint-coupled eviction (turso sole-survivor rule,
            # DESIGN.md §14): if the policy pass left us under pressure,
            # idle sequences whose only version is durably checkpointed are
            # holding pages no policy can touch — current versions are
            # always needed.  Durable storage has their data; drop them.
            if self.ckpt_max >= 0 and self._fetch(
                    self._gate(self.st).under_pressure):
                self.st, ck_pages, n_ev = self._evict(
                    self.st, np.int32(self.ckpt_max))
                n_ev, ck_pages = map(int, self._fetch((n_ev, ck_pages)))
                self.stats.note_ckpt_eviction(n_ev, ck_pages)
                freed += ck_pages
            self.stats.note_reclaim(freed, self._live_pages())

    def _retrying(self, what: str, op, *args, mask: jax.Array,
                  peak: bool = True) -> np.ndarray:
        """Run pool program ``op(st, *args, mask)`` in a ``what`` span and
        retry its failed lanes after a reclaim pass, up to
        ``max_reclaim_rounds`` times, each failure a pressure event; with
        ``peak`` the live-page peak is noted after every run.  ``failed``
        is read once per round; returns its last host copy (the lanes
        that gave up) with the give-ups counted."""
        rounds = 0
        while True:
            with span(what):
                # assigned straight to self.st: a local would keep this
                # state (and its copy of the page pool) alive through the
                # reclaim and retry
                self.st, mask = op(self.st, *args, mask)
            failed = self._fetch(mask)
            if peak:
                self._note_peak()
            if not failed.any() or rounds >= self.max_reclaim_rounds:
                break
            self.stats.note_event()
            self._reclaim_once(extra_deficit=int(failed.sum()))
            rounds += 1
        self.stats.give_ups += int(failed.sum())
        return failed

    def step(self, seq_ids: jax.Array, k_new: jax.Array, v_new: jax.Array,
             mask: jax.Array) -> np.ndarray:
        """Append one token per masked sequence; reclaim-and-retry on
        pressure.  Returns failed[B] on the host (True = gave up after
        reclaims)."""
        with span("repro.engine.step", step=self.counters.steps):
            free_before = self._fetch(self.st.free)
            failed = self._retrying("repro.pool.append", self._append,
                                    seq_ids, k_new, v_new, mask=mask)
            # LWM rule: a watermark crossing is itself a trigger event
            if self._fetch(self._gate(self.st).under_pressure):
                self.stats.note_event()
                self._reclaim_once()
            self._note_freed(free_before)
            self.counters.steps += 1
        return failed

    def _fork_retry(self, src_ids: jax.Array, dst_ids: jax.Array,
                    mask: jax.Array) -> np.ndarray:
        """The fork op proper (COW, or eager when ``eager_fork``) with the
        same reclaim-and-retry discipline as `step` — shared by `fork` and
        `join`, which differ only in lineage bookkeeping."""
        free_before = self._fetch(self.st.free)
        failed = self._retrying("repro.pool.fork", self._fork, src_ids,
                                dst_ids, mask=mask)
        self._note_freed(free_before)
        return failed

    def _current_lengths(self, seq_ids: np.ndarray) -> np.ndarray:
        tbl, has = vstore.current_read(self.st.mv, jnp.asarray(seq_ids))
        tbl, has, lengths = self._fetch((tbl, has, self.st.lengths))
        return np.where(has, lengths[np.maximum(tbl, 0)], 0)

    def fork(self, src_ids: jax.Array, dst_ids: jax.Array,
             mask: jax.Array) -> np.ndarray:
        """First-class COW fork: child ``dst`` adopts parent ``src``'s
        content (sharing full pages unless ``eager_fork``) and enters the
        lineage DAG, so `joins`/`releases`/validators can see it.  Returns
        failed[B]."""
        failed = self._fork_retry(src_ids, dst_ids, mask)
        mask, src_np, dst_np = self._fetch((mask, src_ids, dst_ids))
        ok = mask & ~failed
        if ok.any():
            ts = int(self._fetch(self.st.mv.now))
            lens = self._current_lengths(dst_np)
            for i in np.flatnonzero(ok):
                self.dag.fork(int(src_np[i]), int(dst_np[i]), ts,
                              int(lens[i]))
        return failed

    def join(self, src_ids: jax.Array, dst_ids: jax.Array,
             mask: jax.Array) -> np.ndarray:
        """Join child ``src`` back into ``dst``: the target adopts the
        child's content as its next descriptor version (a fork write onto
        the target slot — pages stay shared) and the child slot is released.
        Grandchildren are re-parented to the join target.  Returns
        failed[B]."""
        failed = self._fork_retry(src_ids, dst_ids, mask)
        mask, src_np, dst_np = self._fetch((mask, src_ids, dst_ids))
        done = mask & ~failed
        if done.any():
            self.reset(src_np, done)
            for i in np.flatnonzero(done):
                self.dag.join(int(src_np[i]), int(dst_np[i]))
        return failed

    def release(self, seq_ids: jax.Array, mask: jax.Array) -> np.ndarray:
        """Release a branch: recycle the slot and drop it from the lineage
        DAG — its shared pages are freed by the sweep exactly when the last
        descendant holding them goes.  Returns failed[B]."""
        failed = self.reset(seq_ids, mask)
        mask, ids_np = self._fetch((mask, seq_ids))
        for i in np.flatnonzero(mask & ~failed):
            self.dag.release(int(ids_np[i]))
        return failed

    def reset(self, seq_ids: jax.Array, mask: jax.Array) -> np.ndarray:
        """Recycle finished sequences' slots (empty table version); same
        reclaim-and-retry discipline as `step`.  Returns failed[B] on the
        host."""
        with span("repro.engine.reset"):
            free_before = self._fetch(self.st.free)
            failed = self._retrying("repro.pool.reset", self._reset,
                                    seq_ids, mask=mask, peak=False)
            self._note_freed(free_before)
        return failed

    def reclaim(self, deficit: Optional[int] = None) -> int:
        """Explicit GC pass (the engine-level ``gc_step``; API parity with
        ``ShardedPagedKVEngine.reclaim``): chases the gate deficit, or an
        explicit one — a large deficit forces the full cold-spill sweep,
        and with ``ckpt_max`` armed the checkpoint-eviction post-pass runs
        if the pool is still under pressure afterwards.  Counted as one
        pressure event so the reclaims <= pressure_events invariant holds.
        Returns pages freed."""
        free_before = self._fetch(self.st.free)
        before = self._live_pages()
        self.stats.note_event()
        self._reclaim_once(
            extra_deficit=0 if deficit is None else int(deficit))
        self._note_freed(free_before)
        return before - self._live_pages()

    def freed_pages(self) -> List[int]:
        """Drain the handles of pages recycled since the last call — exactly
        the loop the module docstring promises: a page appears here once its
        last referencing page-table version was collected, and the allocator
        (the free bitmap) may hand it to any sequence's next append."""
        out, self._freed_pages = self._freed_pages, []
        return out

    # -- durability (DESIGN.md §14) -------------------------------------
    def checkpoint(self, directory: Union[str, os.PathLike,
                                          CheckpointManager],
                   step: Optional[int] = None) -> int:
        """Durably checkpoint the whole engine: the paged-KV pytree (pages,
        free bitmaps, page tables, the full MVState including the retire
        ring and announce board) plus the host-side GC state (ReclaimStats,
        fork DAG, pending freed-page handles).  Returns the manifest step.

        Success *arms* the sole-survivor rule: ``ckpt_max`` advances to the
        store clock, so every version written up to now is durable and an
        idle sequence's sole surviving version may be evicted under pressure
        — `restore` can always bring it back."""
        mgr = (directory if isinstance(directory, CheckpointManager)
               else CheckpointManager(os.fspath(directory)))
        ts = int(self._fetch(self.st.mv.now))
        step = ts if step is None else int(step)
        extra = {
            "stats": dataclasses.asdict(self.stats),
            "dag": self.dag.as_dict(),
            "freed_pages_pending": [int(p) for p in self._freed_pages],
            "ckpt_max": ts,
        }
        mgr.save(step, self.st, extra=extra)
        self.ckpt_max = ts
        return step

    def restore(self, directory: Union[str, os.PathLike, CheckpointManager],
                step: Optional[int] = None) -> int:
        """Inverse of `checkpoint`: replace the device pytree and replay the
        host-side GC state (retire ring and announce board ride in the
        pytree; stats/DAG/pending-frees come from the manifest extras), so
        reclamation resumes exactly where the saved engine left off.
        ``step=None`` restores the latest manifest."""
        mgr = (directory if isinstance(directory, CheckpointManager)
               else CheckpointManager(os.fspath(directory)))
        if step is None:
            step = mgr.latest_step()
            if step is None:
                raise FileNotFoundError(
                    f"no checkpoint manifest under {mgr.dir!r}")
        tree, extra = mgr.restore(int(step), like=self.st)
        self.st = jax.tree_util.tree_map(jnp.asarray, tree)
        self.stats = ReclaimStats(**extra.get("stats", {}))
        self.dag = ForkDAG.from_dict(extra.get("dag", {}))
        self._freed_pages = [int(p) for p in
                             extra.get("freed_pages_pending", [])]
        self.ckpt_max = int(extra.get("ckpt_max", -1))
        return int(step)

    def pin(self, lane: int) -> int:
        with span("repro.snapshot.pin"):
            mv, ts = _pin(self.st.mv, np.int32(lane))
            self.st = self.st._replace(mv=mv)
            return int(self._fetch(ts))

    def unpin(self, lane: int) -> None:
        with span("repro.snapshot.unpin"):
            self.st = self.st._replace(mv=_unpin(self.st.mv, np.int32(lane)))

    def view_at(self, t: int, seq_ids: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, jax.Array]:
        """(page_table[B, MP], lengths[B]) of ``seq_ids`` (default: every
        sequence) as of pinned time ``t``."""
        if seq_ids is None:
            seq_ids = self._all_seqs
        with span("repro.snapshot.read"):
            # the read takes the tables and descriptors, never the pool
            return self._read(self.st._replace(k_pages=None, v_pages=None),
                              seq_ids, np.int32(t))

    def space(self) -> Dict[str, int]:
        rep = vstore.space_report(self.st.mv)
        rep["live_pages"] = self._live_pages()
        rep["free_pages"] = self.st.free.shape[0] - rep["live_pages"]
        rep["peak_pages"] = self.peak_pages
        rep["peak_pages_post_reclaim"] = self.peak_pages_post_reclaim
        rep["pages_reclaimed"] = self.pages_reclaimed
        rep["pressure_events"] = self.pressure_events
        rep["reclaims_triggered"] = self.reclaims_triggered
        rep["give_ups"] = self.give_ups
        rep["forks"] = self.forks
        rep["joins"] = self.joins
        rep["releases"] = self.releases
        rep["ckpt_evictions"] = self.stats.ckpt_evictions
        rep["ckpt_pages_freed"] = self.stats.ckpt_freed
        rep.update(self.counters.as_row())
        return rep
