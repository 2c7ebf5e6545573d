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
from repro.configs.base import RECURRENT_KINDS, ModelConfig, RunConfig
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
    the snapshot lengths, so the result is atomic w.r.t. ongoing decodes.

    Only a cache that is a history cut by length can be read as of ``t``.
    A recurrent state is overwritten in place each step, so a model with
    recurrent layers is refused: it would read the current state as if it
    were the state at ``t``."""
    recurrent = sorted(set(cfg.layer_pattern) & set(RECURRENT_KINDS))
    if recurrent:
        raise ValueError(
            f"{cfg.name}: snapshot_score cannot read the recurrent state of "
            f"{recurrent} layers as of a pinned time; only its current "
            f"value is held")
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
        """The descriptor store's space, the host counters, and the cache's
        bytes by kind of state (`cache_bytes`)."""
        return {**vstore.space_report(self.state.mv),
                **self.counters.as_row(),
                **cache_bytes(self.cfg, self.state.cache)}


def cache_bytes(cfg: ModelConfig, cache) -> Dict[str, int]:
    """Bytes of the decode cache by kind of state: ``kv_cache_bytes`` (the
    K/V, and the slot positions of windowed layers, of attention layers),
    ``recurrent_state_bytes`` (the states recurrent layers overwrite each
    step) and ``conv_window_bytes`` (their trailing conv inputs)."""
    pat = cfg.layer_pattern
    layers = [(kind, cache["sb"][f"l{i}"]) for i, kind in enumerate(pat)]
    layers += [(pat[i % len(pat)], c) for i, c in enumerate(cache["tail"])]
    out = {"kv_cache_bytes": 0, "recurrent_state_bytes": 0,
           "conv_window_bytes": 0}
    for kind, c in layers:
        for field, leaf in zip(c._fields, c):
            if kind not in RECURRENT_KINDS:
                key = "kv_cache_bytes"
            elif field == "conv":
                key = "conv_window_bytes"
            else:
                key = "recurrent_state_bytes"
            out[key] += int(leaf.size) * leaf.dtype.itemsize
    return out


def _manager(directory: Union[str, os.PathLike, CheckpointManager]
             ) -> CheckpointManager:
    return (directory if isinstance(directory, CheckpointManager)
            else CheckpointManager(os.fspath(directory)))


class PagedLoop:
    """The paged cache's serving loop with synchronous pressure
    reclamation, written once for every engine that runs it.

    ``step`` appends one token per masked sequence.  A failed append (page
    pool, table pool, or descriptor slab exhausted) is a **pressure event**:
    the loop reclaims synchronously — hot-sequence-first descriptor
    compaction, then the reachability sweep that recycles pages — and
    retries the failed lanes, up to ``gc.max_reclaim_rounds`` before giving
    up (turso's trigger-on-event rule; the sim's abort => reclaim => retry
    loop).  A post-step watermark crossing triggers the same pass without a
    failure.  Accounting lives in one
    :class:`repro.core.telemetry.ReclaimStats` (``self.stats``); the
    schema-v4 counter names (``pressure_events``, ``reclaims_triggered``,
    ``pages_reclaimed``, ``peak_pages``, ``peak_pages_post_reclaim``,
    ``give_ups``) survive as read-only properties feeding BENCH_serve rows
    directly.

    Every program it launches has a stable name (``jit_pool_*``,
    ``jit_gc_*``, ``jit_snapshot_*`` in a device trace), each call writes a
    ``repro.*`` span (`repro.core.telemetry.span`), and every device value
    it reads on the host goes through `repro.core.telemetry.fetch`, counted
    in ``counters.host_syncs``.

    Each state-changing program returns, beside the state, what the loop
    needs to choose its next move: the failed lanes, the live page count
    and the watermark gate of the state it leaves.  The loop reads them
    with one fetch per round: a step with nothing failed and no watermark
    crossed runs one program and reads once; each reclaim round adds the
    reclaim program and the retried op, dispatched back to back, and one
    read.  Reports are read with ``[..., :]`` indexing, so a state whose
    every leaf has a leading host dim, and whose reports are ``[H, ...]``,
    runs the same loop: counts are summed over hosts, and a gate crossed on
    any host is crossed.

    ``self.st`` is consumed by each op: the append, fork, reset, reclaim
    and eviction programs take ownership of the state they are given
    (donated buffers), so the page pool is updated in place and never
    copied.  A caller must not hold ``self.st``, or any leaf of it, across
    an op: read it afresh after each one.

    An engine built on the loop gives it the state, the freed-page bits the
    programs fold (or None, for none) and its `GCConfig`; it may change how
    a program is compiled (`_program`), the pins every program honours
    beside the state's own board (`_pins`) and what a checkpoint holds
    beside the state (`_extras`, `_load_extras`)."""

    def __init__(self, st, freed, gc: GCConfig, eager_fork: bool = False):
        self.st, self._freed, self.gc = st, freed, gc
        kern = gc.kernel_kwargs()
        policy = gc.policy

        def opened(st, freed, first):
            """The freed-since-drain bits (pages freed by earlier ops, the
            free bitmap at the current op's start) as a program leaves
            them: the first program of an engine op folds in what the op
            before it freed, end against start, and notes the new start."""
            if freed is None:
                return None
            acc, start = freed
            return (jnp.where(first, acc | (st.free & ~start), acc),
                    jnp.where(first, st.free, start))

        def op_result(st, freed, first, out_failed):
            """A pool program's (state, freed bits, failed, failed count,
            report): the report is what the host reads, in one transfer:
            i32[B + 2], the failed lanes, then the live count and the
            watermark gate of the state left."""
            out, failed = out_failed
            gate = paged.page_pressure(out, watermark=gc.page_watermark)
            report = jnp.concatenate([
                failed.astype(jnp.int32),
                jnp.stack([gate.live, gate.under_pressure.astype(jnp.int32)])])
            return (out, opened(st, freed, first), failed, failed.sum(),
                    report)

        # one named function per program, so that a device trace shows
        # ``jit_<name>`` (a jitted ``partial`` runs as ``jit__unknown``);
        # ``pins`` are the ``extra_pins`` of `_pins`
        def pool_append(st, freed, first, seq_ids, k_new, v_new, mask,
                        pins=None):
            return op_result(st, freed, first, paged.append_tokens(
                st, seq_ids, k_new, v_new, mask, gc_policy=policy,
                extra_pins=pins, **kern))

        def pool_fork(st, freed, first, src_ids, dst_ids, mask, pins=None):
            return op_result(st, freed, first, paged.fork_sequence(
                st, src_ids, dst_ids, mask, gc_policy=policy,
                copy_pages=eager_fork, extra_pins=pins, **kern))

        def pool_reset(st, freed, first, seq_ids, mask, pins=None):
            return op_result(st, freed, first, paged.reset_sequence(
                st, seq_ids, mask, gc_policy=policy, extra_pins=pins,
                **kern))

        def pool_live(st):
            return paged.live_pages(st)

        def gc_reclaim(st, freed, first, extra, pins=None):
            """One reclaim pass: the gate's deficit, raised to ``extra``
            (the failed lanes that called it) and to 1, chased from the
            hot sequences.  Returns (state, freed bits, report): i32[3],
            the pages freed, then the live count and the watermark gate of
            the state left."""
            gate = paged.page_pressure(st, watermark=gc.page_watermark)
            deficit = jnp.maximum(jnp.maximum(gate.deficit, extra), 1)
            out, pages = paged.reclaim_on_pressure(
                st, paged.hot_sequences(st, k=gc.hot_k), deficit,
                gc_policy=policy, extra_pins=pins, **kern)
            after = paged.page_pressure(out, watermark=gc.page_watermark)
            return out, opened(st, freed, first), jnp.stack([
                pages, after.live, after.under_pressure.astype(jnp.int32)])

        def gc_evict(st, freed, ckpt_max, pins=None):
            """Returns (state, freed bits, report): i32[3], the pages
            freed, the versions evicted and the live count of the state
            left.  It runs only behind a reclaim pass, never first."""
            out, pages, n_ev = paged.evict_checkpointed(st, ckpt_max, pins)
            return out, freed, jnp.stack([pages, n_ev, paged.live_pages(out)])

        def snapshot_read(st, seq_ids, t):
            return paged.snapshot_view(st, seq_ids, t, **kern)

        self._append = self._program(pool_append)
        self._fork = self._program(pool_fork)
        self._reset = self._program(pool_reset)
        self._live = self._program(pool_live, donate=False)
        self._reclaim = self._program(gc_reclaim)
        self._evict = self._program(gc_evict)
        # a snapshot read is of one host's tables
        self._read = jax.jit(snapshot_read)
        self.counters = EngineCounters()
        # whether a program is the first of an engine op
        self._first, self._later = (jax.device_put(np.bool_(b))
                                    for b in (True, False))
        self.stats = ReclaimStats(unit="pages")
        #: highest durably checkpointed timestamp; -1 = no checkpoint taken.
        #: Setting it (via `checkpoint()`) arms the sole-survivor eviction
        #: rule in `_reclaim_pass` (DESIGN.md §14).
        self.ckpt_max: int = -1

    def _program(self, fn, donate: bool = True):
        """The program that runs ``fn``.  With ``donate`` it takes
        ownership of its first two arguments, the state and the freed bits;
        the state outlives the programs that only read it."""
        return jax.jit(fn, donate_argnums=(0, 1) if donate else ())

    def _pins(self):
        """The ``extra_pins`` the programs of an op honour, taken before
        its first program and again each reclaim round.  On one host the
        board holds every pin: None, an empty pytree, adds nothing to a
        program's arguments or its HLO."""
        return None

    def _extras(self) -> Dict[str, Any]:
        """What a checkpoint's manifest holds beside ``stats`` and
        ``ckpt_max``."""
        return {}

    def _load_extras(self, tree, extra: Dict[str, Any]) -> None:
        """Replay `_extras` from a restored manifest; ``tree`` is the
        restored state on the host."""

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

    def _fetch(self, tree):
        return fetch(tree, self.counters)

    def _run(self, what: str, op, first: jax.Array, pins, *args
             ) -> List[jax.Array]:
        """Dispatch pool program ``op`` in a ``what`` span; returns its
        device (failed, failed count, report)."""
        with span(what):
            self.st, self._freed, *out = op(self.st, self._freed, first,
                                            *args, pins)
        return out

    def _reclaim_pass(self, extra, first: jax.Array, pins
                      ) -> Tuple[jax.Array, Optional[jax.Array]]:
        """Dispatch one reclaim pass, chasing at least ``extra`` pages, in a
        ``repro.gc.reclaim`` span; returns its device reports (the pass's,
        the eviction's or None) for `_note_reclaim`, read with the round's
        fetch.

        Checkpoint-coupled eviction (turso sole-survivor rule, DESIGN.md
        §14): if the policy pass left the pool under pressure, idle
        sequences whose only version is durably checkpointed hold pages no
        policy can touch (current versions are always needed).  Durable
        storage has their data; drop them.  Deciding that takes a read of
        the gate of its own, made only while a checkpoint is armed."""
        with span("repro.gc.reclaim"):
            self.st, self._freed, report = self._reclaim(
                self.st, self._freed, first, extra, pins)
            if self.ckpt_max >= 0 and self._fetch(report)[..., 2].any():
                self.st, self._freed, evicted = self._evict(
                    self.st, self._freed, np.int32(self.ckpt_max), pins)
                return report, evicted
            return report, None

    def _note_reclaim(self, report, evicted) -> int:
        """Count one reclaim pass from its fetched reports; returns the
        pages it freed."""
        freed, live, _ = np.reshape(report, (-1, 3)).sum(axis=0)
        if evicted is not None:
            ck_pages, n_ev, live = np.reshape(evicted, (-1, 3)).sum(axis=0)
            self.stats.note_ckpt_eviction(int(n_ev), int(ck_pages))
            freed += ck_pages
        self.stats.note_reclaim(int(freed), int(live))
        return int(freed)

    def _retrying(self, what: str, op, *args, mask: jax.Array,
                  peak: bool = True, watermark: bool = False) -> np.ndarray:
        """Run pool program ``op(st, freed, first, *args, mask, pins)`` in a
        ``what`` span and retry its failed lanes after a reclaim pass, up to
        ``max_reclaim_rounds`` times, each failure a pressure event; with
        ``peak`` the live-page peak is noted after every run.  A round is
        one fetch: the reclaim pass and the retried op are dispatched back
        to back, on the device's failed mask and count.  With
        ``watermark``, a state left under the page watermark is itself a
        trigger event: one more pass runs, and reads once.  Returns the
        last failed lanes on the host (those that gave up, counted)."""
        pins = self._pins()
        failed, n_failed, report = self._run(what, op, self._first, pins,
                                             *args, mask)
        passed = None
        rounds = 0
        while True:
            report_h, passed_h = self._fetch((report, passed))
            failed_h = report_h[..., :-2].astype(bool)
            if passed_h is not None:
                self._note_reclaim(*passed_h)
            if peak:
                self.stats.note_live(int(report_h[..., -2].sum()))
            if not failed_h.any() or rounds >= self.gc.max_reclaim_rounds:
                break
            self.stats.note_event()
            pins = self._pins()
            passed = self._reclaim_pass(n_failed, self._later, pins)
            failed, n_failed, report = self._run(what, op, self._later,
                                                 pins, *args, failed)
            rounds += 1
        self.stats.give_ups += int(failed_h.sum())
        # LWM rule: a watermark crossing is itself a trigger event
        if watermark and report_h[..., -1].any():
            self.stats.note_event()
            self._note_reclaim(*self._fetch(
                self._reclaim_pass(np.int32(0), self._later, pins)))
        return failed_h

    def step(self, seq_ids: jax.Array, k_new: jax.Array, v_new: jax.Array,
             mask: jax.Array) -> np.ndarray:
        """Append one token per masked sequence; reclaim-and-retry on
        pressure.  Returns failed, shaped as ``mask``, on the host (True =
        gave up after reclaims)."""
        with span("repro.engine.step", step=self.counters.steps):
            failed = self._retrying("repro.pool.append", self._append,
                                    seq_ids, k_new, v_new, mask=mask,
                                    watermark=True)
            self.counters.steps += 1
        return failed

    def _fork_retry(self, src_ids: jax.Array, dst_ids: jax.Array,
                    mask: jax.Array) -> np.ndarray:
        """The fork op proper (COW, or eager when ``eager_fork``) with the
        same reclaim-and-retry discipline as `step`."""
        return self._retrying("repro.pool.fork", self._fork, src_ids,
                              dst_ids, mask=mask)

    def reset(self, seq_ids: jax.Array, mask: jax.Array) -> np.ndarray:
        """Recycle finished sequences' slots (empty table version); same
        reclaim-and-retry discipline as `step`.  Returns failed on the
        host."""
        with span("repro.engine.reset"):
            failed = self._retrying("repro.pool.reset", self._reset,
                                    seq_ids, mask=mask, peak=False)
            # The live count a reset leaves, as a program of its own and
            # never read: the benchmark's trace test
            # (tests/chipbench/test_chipbench_program_trace.py) expects
            # ``jit_pool_live`` among an engine call's programs.  It costs a
            # reset one small program and no host read.
            self._live(self.st)
        return failed

    def reclaim(self, deficit: Optional[int] = None) -> int:
        """Explicit GC pass (the engine-level ``gc_step``): chases the gate
        deficit, or an explicit one — a large deficit forces the full
        cold-spill sweep — and with ``ckpt_max`` armed the
        checkpoint-eviction post-pass runs if the pool is still under
        pressure afterwards.  Counted as one pressure event so the
        reclaims <= pressure_events invariant holds.  Returns pages
        freed."""
        self.stats.note_event()
        return self._note_reclaim(*self._fetch(self._reclaim_pass(
            np.int32(0 if deficit is None else deficit), self._first,
            self._pins())))

    # -- durability (DESIGN.md §14) -------------------------------------
    def checkpoint(self, directory: Union[str, os.PathLike,
                                          CheckpointManager],
                   step: Optional[int] = None) -> int:
        """Durably checkpoint the whole engine: the state pytree (pages,
        free bitmaps, page tables, the full MVState including the retire
        ring and announce board) plus the host-side GC state (ReclaimStats
        and the engine's `_extras`).  Returns the manifest step.

        Success *arms* the sole-survivor rule: ``ckpt_max`` advances to the
        store clock — the slowest host's, as a version is durable
        everywhere only once every host has passed it — so every version
        written up to now is durable and an idle sequence's sole surviving
        version may be evicted under pressure: `restore` can always bring
        it back."""
        mgr = _manager(directory)
        ts = int(np.min(self._fetch(self.st.mv.now)))
        step = ts if step is None else int(step)
        extra = {"stats": dataclasses.asdict(self.stats), **self._extras(),
                 "ckpt_max": ts}
        mgr.save(step, self.st, extra=extra)
        self.ckpt_max = ts
        return step

    def restore(self, directory: Union[str, os.PathLike, CheckpointManager],
                step: Optional[int] = None) -> int:
        """Inverse of `checkpoint`: replace the device pytree and replay the
        host-side GC state (retire ring and announce board ride in the
        pytree; stats and the engine's extras come from the manifest), so
        reclamation resumes exactly where the saved engine left off.
        ``step=None`` restores the latest manifest."""
        mgr = _manager(directory)
        if step is None:
            step = mgr.latest_step()
            if step is None:
                raise FileNotFoundError(
                    f"no checkpoint manifest under {mgr.dir!r}")
        tree, extra = mgr.restore(int(step), like=self.st)
        self.st = jax.tree_util.tree_map(jnp.asarray, tree)
        self.stats = ReclaimStats(**extra.get("stats", {}))
        self.ckpt_max = int(extra.get("ckpt_max", -1))
        self._load_extras(tree, extra)
        return int(step)


class PagedKVEngine(PagedLoop):
    """`PagedLoop` over one host's paged cache — the `freed_pages()`
    contract the module docstring promises, made concrete.  Beside the
    loop it keeps what only one host has: the fork lineage DAG (`fork`,
    `join`, `release`), the drain of freed pages (the pool programs fold
    the pages they free into a device bitmap that only `freed_pages()` and
    `checkpoint()` read), snapshot pins by lane (`pin`, `unpin`,
    `view_at`) and its `space()` row.

    Configuration lives in one :class:`repro.core.telemetry.GCConfig`
    (``gc=``); the old per-kwarg spellings (``versions_per_seq``,
    ``gc_policy``, ``page_watermark``, ...) still work for one release but
    emit ``DeprecationWarning`` (DESIGN.md §13)."""

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
        # the freed bits: (pages freed by ops since the last
        # `freed_pages()`, the free bitmap at the current op's start),
        # bool[num_pages] each.  An all-free start counts nothing freed
        # before the first op.
        super().__init__(
            paged.make_paged_kv(num_seqs, num_pages, page_size,
                                max_pages_per_seq, kv_heads, head_dim,
                                gc=cfg, dtype=dtype),
            self._drained(np.ones(num_pages, bool)), cfg, eager_fork)
        self.gc_policy = cfg.policy
        self._all_seqs = jnp.arange(num_seqs, dtype=jnp.int32)
        self.dag = ForkDAG()

    @property
    def forks(self) -> int:
        return self.dag.forks

    @property
    def joins(self) -> int:
        return self.dag.joins

    @property
    def releases(self) -> int:
        return self.dag.releases

    @staticmethod
    def _drained(start: np.ndarray, pending=()
                 ) -> Tuple[jax.Array, jax.Array]:
        """Freed bits holding ``pending`` pages, with ``start`` as the free
        bitmap at the current op's start."""
        acc = np.zeros(start.shape, bool)
        acc[np.asarray(pending, np.int64)] = True
        return jax.device_put((acc, np.asarray(start)))

    @staticmethod
    def _pending(freed, free) -> np.ndarray:
        """The pages freed since the last drain, from fetched bits and the
        free bitmap now."""
        acc, start = freed
        return acc | (free & ~start)

    def _live_pages(self) -> int:
        return int(self._fetch(self._live(self.st)))

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

    def freed_pages(self) -> List[int]:
        """Drain the handles of pages recycled since the last call — exactly
        the loop the module docstring promises: a page appears here once its
        last referencing page-table version was collected, and the allocator
        (the free bitmap) may hand it to any sequence's next append.  Pages
        freed and handed out again since the last call are left out: every
        handle returned is free when it is returned.  One host read."""
        freed, free = self._fetch((self._freed, self.st.free))
        self._freed = self._drained(free)
        return [int(p) for p in np.flatnonzero(self._pending(freed, free)
                                               & free)]

    def _extras(self) -> Dict[str, Any]:
        """The fork DAG and the freed pages not yet drained."""
        return {
            "dag": self.dag.as_dict(),
            "freed_pages_pending": [int(p) for p in np.flatnonzero(
                self._pending(*self._fetch((self._freed, self.st.free))))],
        }

    def _load_extras(self, tree, extra: Dict[str, Any]) -> None:
        self.dag = ForkDAG.from_dict(extra.get("dag", {}))
        self._freed = self._drained(tree.free,
                                    extra.get("freed_pages_pending", []))

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
