"""Sharded multi-host MVGC: partitioned version store + global-LWM
reclamation (DESIGN.md §13, ROADMAP item 3).

The deployable stack (``core.mvgc.vstore`` slabs governing the
``mvkv.paged`` page pool) scales out by *partitioning state, not the
protocol*: every leaf of the paged-KV state gains a leading ``[H]`` host dim
(:func:`stack_states`), placed one-slice-per-mesh-position by
``repro.dist.sharding.host_stacked_sharding``, and the single-host step
functions run unchanged on each shard (``jax.vmap`` over the host dim — the
shard boundary and the vmap boundary coincide, so XLA keeps every op
host-local).  Announcement lanes stay **host-local**: a reader pins on its
own host's board and nothing else moves.

What crosses hosts is one number: the **global low-water mark**.  Each GC
step gathers every host's oldest pin (:func:`lwm_contributions`; a pin-free
host contributes the ``TS_MAX`` identity), ages out hosts whose announcement
is staler than their watchdog budget (:func:`age_out_stale` — a stalled host
*bounds* reclamation for its budget, never blocks it), reduces with the
``reduce="min"`` ring all-reduce (``repro.dist.overlap``), and injects the
result into every shard's GC as ``extra_pins`` — so no shard ever reclaims a
version pinned by *any* live host, and EBR's epoch bound becomes
``min(local oldest, global LWM)``.

Telemetry speaks the unified vocabulary: the vmapped capacity gates return
:class:`repro.core.telemetry.PressureSignal` with ``[H]`` vector fields, and
the engine accounts into one :class:`repro.core.telemetry.ReclaimStats`
(plus ``stale_lanes_aged`` / ``lwm_advances``), feeding ``BENCH_dist.json``.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.mvgc.pool import EMPTY, TS_MAX
from repro.core.telemetry import GCConfig, PressureSignal, ReclaimStats
from repro.dist.overlap import make_ring_all_reduce
from repro.dist.sharding import host_stacked_sharding
from repro.dist.straggler import StepWatchdog
from repro.mvkv import paged


# ---------------------------------------------------------------------------
# host-stacked state
# ---------------------------------------------------------------------------
def stack_states(base, hosts: int):
    """Host-stack a single-host state tree: every array leaf gains a leading
    ``[H]`` dim (one identical copy per host).  The result composes with
    ``host_stacked_sharding`` for placement and with ``jax.vmap`` for
    running the single-host step functions shard-locally."""
    return jax.tree.map(
        lambda x: jnp.tile(x[None], (hosts,) + (1,) * x.ndim), base)


def lwm_contributions(st: paged.PagedKV) -> jax.Array:
    """i32[H]: each host's LWM contribution — the oldest timestamp pinned on
    its (host-local) announcement board, or the ``TS_MAX`` sentinel when the
    board is pin-free.  The sentinel is the identity of ``min``, so idle
    hosts drop out of the global reduction instead of capping it at their
    own clock (see ``announce.lwm`` for the single-board form)."""
    slots = st.mv.board.slots                       # [H, P]
    return jnp.where(slots != EMPTY, slots, TS_MAX).min(axis=1) \
        .astype(jnp.int32)


def age_out_stale(contrib: jax.Array, ages_s, budget_s
                  ) -> Tuple[jax.Array, jax.Array]:
    """Straggler tolerance: replace stale hosts' contributions with the
    ``TS_MAX`` sentinel.  ``ages_s[H]`` is the age of each host's last
    announcement refresh; a host whose age exceeds ``budget_s`` (scalar or
    ``[H]``, typically ``GCConfig.stale_after_s`` or
    ``StepWatchdog.budget_s``) is presumed stalled and aged out — its pins
    stop holding back the mesh-wide LWM, so one wedged host *bounds* (never
    blocks) everyone else's reclamation.  Returns ``(aged[H], n_aged)``
    where ``n_aged`` counts the lanes actually aged out (hosts that were
    both stale and pinning)."""
    contrib = jnp.asarray(contrib, jnp.int32)
    ages = jnp.asarray(ages_s, jnp.float32)
    budget = jnp.broadcast_to(jnp.asarray(budget_s, jnp.float32), ages.shape)
    stale = ages > budget
    aged = jnp.where(stale, TS_MAX, contrib)
    n_aged = (stale & (contrib != TS_MAX)).sum().astype(jnp.int32)
    return aged, n_aged


def global_lwm(contrib: jax.Array, ring=None) -> jax.Array:
    """Mesh-wide LWM: ``min`` over the per-host contributions, i32[].

    ``ring`` is a ``make_ring_all_reduce(mesh, axis, reduce="min")`` callable
    when the contributions are sharded over a real mesh axis — the 2(n-1)-hop
    ppermute ring does the cross-host combine and leaves every position
    holding the reduced vector; the trailing ``min`` is then shard-locally
    trivial.  With ``ring=None`` (single device / unsharded test states) the
    plain reduction computes the same value."""
    red = ring(contrib) if ring is not None else contrib
    return red.min().astype(jnp.int32)


# ---------------------------------------------------------------------------
# sharded serving engine
# ---------------------------------------------------------------------------
def _per_shard(fn, mesh, axis: str):
    """``jit`` of ``fn`` vmapped over the hosts each device holds.  Every
    argument and result is ``[H, ...]``-leading and split over ``axis`` by
    a ``shard_map``, so each device runs the single-host op on its own
    shards and nothing crosses devices; the GC kernels inside could not be
    split by XLA's partitioner anyway."""
    spec = jax.sharding.PartitionSpec(axis)
    return jax.jit(jax.shard_map(jax.vmap(fn), mesh=mesh, in_specs=spec,
                                 out_specs=spec, check_vma=False))


def _host_slice(x: jax.Array, host: int) -> jax.Array:
    """``x[host]`` for a host-stacked leaf, read from the one device that
    holds that host's shard.  Indexing the sharded array itself would leave
    the slice laid out over the whole mesh, and a Pallas kernel fed from it
    would have to be partitioned, which Mosaic cannot do."""
    for shard in x.addressable_shards:
        rows = range(x.shape[0])[shard.index[0]]
        if host in rows:
            return shard.data[host - rows.start]
    raise ValueError(f"host {host} is not held by this process")


def _per_host(failed: jax.Array) -> np.ndarray:
    """i32[H]: failed lanes per host, the deficit each shard must chase."""
    return np.asarray(failed).sum(axis=1).astype(np.int32)


class ShardedPagedKVEngine:
    """Multi-host paged-KV serving with global-LWM reclamation.

    ``hosts`` logical shards, each owning ``num_seqs`` sequences and
    ``num_pages`` pool pages, stacked along a leading ``[H]`` dim and placed
    over ``mesh`` (default :func:`repro.launch.mesh.make_gc_mesh`).  On a
    mesh of several devices the stack is built sharded, ``hosts / devices``
    shards per device, and a host count the mesh does not divide raises; on
    a one-device mesh it stays unsharded and the LWM reduction is a plain
    ``min`` — the protocol is placement-independent.  All batched entry
    points take ``[H, ...]``-leading arguments.

    Every GC-bearing step first refreshes the global LWM (contributions ->
    staleness aging -> ring-min) and threads it through the shard ops as
    ``extra_pins``, so reclamation on any shard respects every live host's
    pins.  Per-host :class:`StepWatchdog` instances supply the staleness
    budget when ``gc.stale_after_s`` is inf; ``virtual_ages_s`` lets tests
    and the dist bench inject deterministic announcement ages instead of
    wall clock."""

    def __init__(self, hosts: int, num_seqs: int, num_pages: int,
                 page_size: int, max_pages_per_seq: int, kv_heads: int,
                 head_dim: int, *, gc: Optional[GCConfig] = None,
                 mesh=None, dtype=jnp.float32):
        cfg = gc if gc is not None else GCConfig()
        self.gc = cfg
        self.hosts = hosts
        if mesh is None:
            from repro.launch.mesh import make_gc_mesh
            mesh = make_gc_mesh(hosts)
        self.mesh = mesh
        axis = mesh.axis_names[0]
        n = mesh.shape[axis]

        def build():
            return stack_states(
                paged.make_paged_kv(num_seqs, num_pages, page_size,
                                    max_pages_per_seq, kv_heads, head_dim,
                                    gc=cfg, dtype=dtype), hosts)

        if n > 1:
            if hosts % n:
                raise ValueError(
                    f"{hosts} hosts do not divide over the {n}-device mesh "
                    f"axis {axis!r}")
            # built in place, shard by shard: the whole stack would not fit
            # on one device
            shardings = host_stacked_sharding(jax.eval_shape(build), mesh,
                                              axis)
            self.st = jax.jit(build, out_shardings=shardings)()
            self._ring = jax.jit(make_ring_all_reduce(mesh, axis,
                                                      reduce="min"))
        else:
            self.st = build()
            self._ring = None

        kern = cfg.kernel_kwargs()

        def _append(s, seq, k, v, m, pins):
            return paged.append_tokens(s, seq, k, v, m,
                                       gc_policy=cfg.policy,
                                       extra_pins=pins, **kern)

        def _reset(s, seq, m, pins):
            return paged.reset_sequence(s, seq, m, gc_policy=cfg.policy,
                                        extra_pins=pins, **kern)

        def _fork(s, src, dst, m, pins):
            return paged.fork_sequence(s, src, dst, m, gc_policy=cfg.policy,
                                       extra_pins=pins, **kern)

        def _reclaim(s, hot, deficit, pins):
            return paged.reclaim_on_pressure(s, hot, deficit,
                                             gc_policy=cfg.policy,
                                             extra_pins=pins, **kern)

        def _evict(s, ckpt, pins):
            return paged.evict_checkpointed(s, ckpt, extra_pins=pins)

        per_shard = functools.partial(_per_shard, mesh=mesh, axis=axis)
        self._append = per_shard(_append)
        self._reset = per_shard(_reset)
        self._fork = per_shard(_fork)
        self._reclaim_v = per_shard(_reclaim)
        self._evict_v = per_shard(_evict)
        self._gate = per_shard(functools.partial(
            paged.page_pressure, watermark=cfg.page_watermark))
        self._hot = per_shard(functools.partial(paged.hot_sequences,
                                                k=cfg.hot_k))

        self.watchdogs: List[StepWatchdog] = [StepWatchdog()
                                              for _ in range(hosts)]
        # deterministic announcement ages for tests/benches (None = fresh)
        self.virtual_ages_s: Optional[np.ndarray] = None
        self.stats = ReclaimStats(unit="pages")
        self.lwm_advances = 0
        self._last_lwm = -1
        self.forks = 0
        #: highest durably checkpointed timestamp across the mesh; -1 = no
        #: checkpoint.  Arms the sole-survivor eviction rule on every shard
        #: (DESIGN.md §14).
        self.ckpt_max: int = -1

    # -- global LWM ----------------------------------------------------------
    def ages_s(self) -> np.ndarray:
        """f32[H] announcement-refresh age per host: the injected virtual
        ages when set (deterministic tests/benches), else zero — in a real
        deployment this is each host's ``HeartbeatFile.age_s``."""
        if self.virtual_ages_s is not None:
            return np.asarray(self.virtual_ages_s, np.float32)
        return np.zeros((self.hosts,), np.float32)

    def budget_s(self) -> np.ndarray:
        """f32[H] staleness budget per host: ``gc.stale_after_s`` when
        finite, else each host's always-finite ``StepWatchdog.budget_s``
        (the inf-vs-inf warmup hole is closed there)."""
        if math.isfinite(self.gc.stale_after_s):
            return np.full((self.hosts,), self.gc.stale_after_s, np.float32)
        return np.asarray([wd.budget_s() for wd in self.watchdogs],
                          np.float32)

    def lwm_pins(self) -> jax.Array:
        """One global-LWM refresh: contributions -> staleness aging ->
        ring-min.  Returns the per-host ``extra_pins`` array ``i32[H, 1]``
        (every host gets the same mesh-wide LWM) and accounts
        ``stale_lanes_aged`` / ``lwm_advances``."""
        contrib = lwm_contributions(self.st)
        aged, n_aged = age_out_stale(contrib, self.ages_s(), self.budget_s())
        self.stats.stale_lanes_aged += int(n_aged)
        lwm = global_lwm(aged, self._ring)
        val = int(lwm)
        # an "advance" is the LWM moving up from a real pin (TS_MAX is the
        # pin-free sentinel, not a position); decreases — a new pin arriving
        # — just retrack
        if 0 <= self._last_lwm < int(TS_MAX) and val > self._last_lwm:
            self.lwm_advances += 1
        self._last_lwm = val
        return jnp.broadcast_to(lwm, (self.hosts, 1))

    # -- accounting ----------------------------------------------------------
    def _note_peak(self) -> None:
        self.stats.note_live(int(self.live_pages()))

    def _reclaim_once(self, pins: jax.Array, extra_deficit=0) -> None:
        """One reclaim pass on every shard.  ``extra_deficit`` (scalar or
        ``[H]``) is each shard's own count of failed lanes, so a shard
        chases exactly what the single-host engine would."""
        gate = self._gate(self.st)
        deficit = jnp.maximum(
            gate.deficit, np.maximum(1, extra_deficit)).astype(jnp.int32)
        self.st, pages = self._reclaim_v(self.st, self._hot(self.st),
                                         deficit, pins)
        freed = int(pages.sum())
        # checkpoint-coupled eviction (DESIGN.md §14): shards still under
        # pressure drop idle sole-survivor sequences that durable storage
        # already holds — pages no policy pass can reach
        if self.ckpt_max >= 0 and bool(
                self._gate(self.st).under_pressure.any()):
            ck = jnp.full((self.hosts,), int(self.ckpt_max), jnp.int32)
            self.st, ck_pages, n_ev = self._evict_v(self.st, ck, pins)
            self.stats.note_ckpt_eviction(int(n_ev.sum()),
                                          int(ck_pages.sum()))
            freed += int(ck_pages.sum())
        self.stats.note_reclaim(freed, int(self.live_pages()))

    # -- batched serving ops (all args [H, ...]-leading) ---------------------
    def step(self, seq_ids: jax.Array, k_new: jax.Array, v_new: jax.Array,
             mask: jax.Array) -> jax.Array:
        """Append one token per masked sequence on every host, with the
        same reclaim-and-retry pressure discipline as ``PagedKVEngine.step``
        — every append and reclaim carries the fresh global LWM.  Returns
        failed[H, B]."""
        pins = self.lwm_pins()
        self.st, failed = self._append(self.st, seq_ids, k_new, v_new,
                                       mask, pins)
        self._note_peak()
        rounds = 0
        while bool(failed.any()) and rounds < self.gc.max_reclaim_rounds:
            self.stats.note_event()
            self._reclaim_once(pins, extra_deficit=_per_host(failed))
            pins = self.lwm_pins()
            self.st, failed = self._append(self.st, seq_ids, k_new, v_new,
                                           failed, pins)
            self._note_peak()
            rounds += 1
        if bool(self._gate(self.st).under_pressure.any()):
            self.stats.note_event()
            self._reclaim_once(pins)
        if bool(failed.any()):
            self.stats.give_ups += int(failed.sum())
        return failed

    def reset(self, seq_ids: jax.Array, mask: jax.Array) -> jax.Array:
        """Recycle finished sequences on every host (empty table version)."""
        pins = self.lwm_pins()
        self.st, failed = self._reset(self.st, seq_ids, mask, pins)
        rounds = 0
        while bool(failed.any()) and rounds < self.gc.max_reclaim_rounds:
            self.stats.note_event()
            self._reclaim_once(pins, extra_deficit=_per_host(failed))
            pins = self.lwm_pins()
            self.st, failed = self._reset(self.st, seq_ids, failed, pins)
            rounds += 1
        if bool(failed.any()):
            self.stats.give_ups += int(failed.sum())
        return failed

    def fork(self, src_ids: jax.Array, dst_ids: jax.Array,
             mask: jax.Array) -> jax.Array:
        """COW fork on every host (src and dst are host-local sequences)."""
        pins = self.lwm_pins()
        self.st, failed = self._fork(self.st, src_ids, dst_ids, mask, pins)
        self._note_peak()
        rounds = 0
        while bool(failed.any()) and rounds < self.gc.max_reclaim_rounds:
            self.stats.note_event()
            self._reclaim_once(pins, extra_deficit=_per_host(failed))
            pins = self.lwm_pins()
            self.st, failed = self._fork(self.st, src_ids, dst_ids,
                                         failed, pins)
            self._note_peak()
            rounds += 1
        if bool(failed.any()):
            self.stats.give_ups += int(failed.sum())
        self.forks += int((np.asarray(mask) & ~np.asarray(failed)).sum())
        return failed

    def reclaim(self, deficit: Optional[int] = None) -> int:
        """Explicit GC pass on every shard against the fresh global LWM
        (the sharded ``gc_step``).  ``deficit=None`` chases each shard's
        gate deficit; a large explicit deficit forces the full cold-spill
        sweep on every shard.  Returns total pages freed."""
        pins = self.lwm_pins()
        before = int(self.live_pages())
        if deficit is None:
            self._reclaim_once(pins)
        else:
            d = jnp.full((self.hosts,), int(deficit), jnp.int32)
            self.st, pages = self._reclaim_v(self.st, self._hot(self.st),
                                             d, pins)
            self.stats.note_reclaim(int(pages.sum()),
                                    int(self.live_pages()))
        return before - int(self.live_pages())

    # -- host-local pins and snapshot reads ----------------------------------
    def pin(self, host: int, lane: int) -> int:
        """Pin ``host``'s current timestamp on its local board lane — the
        announcement never leaves the host; only the LWM reduction sees it.
        Returns the pinned timestamp."""
        now = self.st.mv.now[host]
        slots = self.st.mv.board.slots.at[host, lane].set(now)
        board = self.st.mv.board._replace(slots=slots)
        self.st = self.st._replace(mv=self.st.mv._replace(board=board))
        return int(now)

    def unpin(self, host: int, lane: int) -> None:
        slots = self.st.mv.board.slots.at[host, lane].set(EMPTY)
        board = self.st.mv.board._replace(slots=slots)
        self.st = self.st._replace(mv=self.st.mv._replace(board=board))

    def host_state(self, host: int) -> paged.PagedKV:
        """This host's shard as a plain single-host ``PagedKV`` view, on
        the device that holds it."""
        return jax.tree.map(lambda x: _host_slice(x, host), self.st)

    def view_at(self, host: int, t: int,
                seq_ids: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, jax.Array]:
        """Snapshot-read ``host``'s shard at pinned time ``t`` (page tables
        + visible lengths), exactly ``paged.snapshot_view`` on the slice."""
        # the read needs the page tables and descriptors, not the pool
        local = jax.tree.map(lambda x: _host_slice(x, host),
                             self.st._replace(k_pages=None, v_pages=None))
        if seq_ids is None:
            seq_ids = jnp.arange(local.mv.store.ts.shape[0], dtype=jnp.int32)
        return paged.snapshot_view(local, seq_ids, jnp.int32(t),
                                   **self.gc.kernel_kwargs())

    # -- durability (DESIGN.md §14) -------------------------------------------
    def checkpoint(self, directory, step: Optional[int] = None) -> int:
        """Durably checkpoint the whole host-stacked pytree (every shard's
        pages, tables, retire ring, announce board) plus the engine's
        accounting, then advance ``ckpt_max`` to the slowest shard's clock —
        a version is only durable mesh-wide once *every* shard has passed
        it.  Returns the manifest step."""
        import dataclasses
        import os as _os
        from repro.ckpt.manager import CheckpointManager
        mgr = (directory if isinstance(directory, CheckpointManager)
               else CheckpointManager(_os.fspath(directory)))
        ts = int(jnp.min(self.st.mv.now))
        step = ts if step is None else int(step)
        extra = {
            "stats": dataclasses.asdict(self.stats),
            "forks": self.forks,
            "lwm_advances": self.lwm_advances,
            "last_lwm": self._last_lwm,
            "ckpt_max": ts,
        }
        mgr.save(step, self.st, extra=extra)
        self.ckpt_max = ts
        return step

    def restore(self, directory, step: Optional[int] = None) -> int:
        """Inverse of `checkpoint`: replace the stacked pytree and replay
        the accounting, so mesh-wide reclamation resumes where the saved
        engine left off.  ``step=None`` restores the latest manifest."""
        import os as _os
        from repro.ckpt.manager import CheckpointManager
        mgr = (directory if isinstance(directory, CheckpointManager)
               else CheckpointManager(_os.fspath(directory)))
        if step is None:
            step = mgr.latest_step()
            if step is None:
                raise FileNotFoundError(
                    f"no checkpoint manifest under {mgr.dir!r}")
        tree, extra = mgr.restore(int(step), like=self.st)
        self.st = jax.tree.map(jnp.asarray, tree)
        self.stats = ReclaimStats(**extra.get("stats", {}))
        self.forks = int(extra.get("forks", 0))
        self.lwm_advances = int(extra.get("lwm_advances", 0))
        self._last_lwm = int(extra.get("last_lwm", -1))
        self.ckpt_max = int(extra.get("ckpt_max", -1))
        return int(step)

    # -- telemetry ------------------------------------------------------------
    def live_pages(self) -> jax.Array:
        return (~self.st.free).sum()

    def pressure(self) -> PressureSignal:
        """The unified gate over all shards: ``PressureSignal`` with
        ``[H]`` vector fields (one entry per host)."""
        return self._gate(self.st)

    def space(self) -> Dict[str, int]:
        """Flat counters for BENCH_dist rows: the unified ReclaimStats
        vocabulary plus the dist-only fields."""
        sig = self.pressure()
        rep = dict(self.stats.as_row())
        rep["hosts"] = self.hosts
        rep["live_pages"] = int(self.live_pages())
        rep["free_pages"] = int(self.st.free.sum())
        rep["page_pool"] = int(np.prod(self.st.free.shape))
        rep["under_pressure_hosts"] = int(sig.under_pressure.sum())
        rep["lwm"] = self._last_lwm
        rep["lwm_advances"] = self.lwm_advances
        rep["overflows"] = int(self.st.mv.overflow_count.sum())
        rep["dropped_retires"] = int(self.st.mv.dropped_retires.sum())
        rep["forks"] = self.forks
        return rep
