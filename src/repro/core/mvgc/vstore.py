"""Versioned object store: the deployable MVGC facade.

Bundles the version slabs, announcement board, retire ring and the global
timestamp into one pytree (`MVState`) with pure step functions, and exposes
the paper's scheme menu as GC *policies* over identical state:

* ``ebr``    — free every version whose interval closed before the oldest
               pinned timestamp (epoch quiescence; cannot free "middle"
               versions that closed while any older reader is live).
* ``steam``  — compact-on-append: after each write step, sweep exactly the
               written slots' slabs with needed(A, now).
* ``dlrt``   — RangeTracker ring; flush frees exactly the retired entries
               that became obsolete (the PDL splice-by-handle analogue).
* ``slrt``   — ring flush *plus* a needed-sweep of the implicated slots'
               whole slabs (SSL compact's preemptive splicing; default).
* ``sweep``  — GVM/HANA analogue: sweep every slab each ``gc_every`` steps,
               regardless of update activity (the baseline the paper's
               related work improves on).

All functions are jit/shard_map friendly: fixed shapes, masked updates, no
host control flow on traced values.  Policy strings specialize at trace time.

Every GC entry point takes an optional ``extra_pins`` array of externally
announced timestamps (``TS_MAX`` sentinel = no pin) that is honoured exactly
like a local board lane.  Single-host callers leave it ``None`` (bit-for-bit
the pre-existing behaviour); the sharded stack (``repro.dist.mvgc``)
injects the mesh-wide low-water mark so no shard reclaims a version pinned
by *any* host (DESIGN.md §13).

``gc_step`` / ``reclaim_on_pressure`` additionally take an optional
``ckpt_max`` — the highest durably checkpointed timestamp (``EMPTY`` = no
checkpoint).  It unlocks turso's *sole-survivor* rule (SNIPPETS.md §1,
DESIGN.md §14): a slot's only live version, durable at-or-before
``ckpt_max`` and older than every pin, may be evicted even though it is
current — durable storage has the data, ``restore()`` brings it back.  The
kill is applied as one shared post-pass (:func:`evict_checkpointed`) after
the policy's own collection, so all five policies inherit it with zero
policy-specific code — exactly like the ``extra_pins`` threading.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.mvgc import announce as ann
from repro.core.mvgc import pool, rangetracker as rt
from repro.core.mvgc.needed import needed_intervals, sort_announcements
from repro.core.mvgc.pool import EMPTY, TS_MAX, VersionStore
from repro.core.telemetry import GCConfig, PressureSignal, resolve_kernel
from repro.kernels.compact import ops as compact_ops
from repro.kernels.version_search import ops as search_ops

POLICIES = ("ebr", "steam", "dlrt", "slrt", "sweep")


class MVState(NamedTuple):
    store: VersionStore          # [S, V] version slabs
    board: ann.AnnounceBoard     # [P] reader pins
    ring: rt.RetireRing          # [B] retired intervals (RT policies)
    now: jax.Array               # i32[] global timestamp (one tick per step)
    overflow_count: jax.Array    # i32[] slab-overflow events (monitoring)
    dropped_retires: jax.Array   # i32[] ring-overflow events (monitoring)


def make_state(
    num_slots: int,
    versions_per_slot: Optional[int] = None,
    num_reader_lanes: Optional[int] = None,
    ring_capacity: Optional[int] = None,
    *,
    gc: Optional[GCConfig] = None,
) -> MVState:
    """Build an empty MVState.  Sizing comes from the positional args when
    given, else from ``gc`` (:class:`repro.core.telemetry.GCConfig`), so both
    the legacy ``make_state(S, V, P)`` call shape and the redesigned
    ``make_state(S, gc=cfg)`` shape work."""
    cfg = gc if gc is not None else GCConfig()
    if versions_per_slot is None:
        versions_per_slot = cfg.versions_per_slot
    if num_reader_lanes is None:
        num_reader_lanes = cfg.reader_lanes
    if ring_capacity is None:
        ring_capacity = cfg.ring_capacity
    ring_capacity = ring_capacity or max(64, num_slots // 2)
    return MVState(
        store=pool.make_store(num_slots, versions_per_slot),
        board=ann.make_board(num_reader_lanes),
        ring=rt.make_ring(ring_capacity),
        now=jnp.int32(0),
        overflow_count=jnp.int32(0),
        dropped_retires=jnp.int32(0),
    )


# ---------------------------------------------------------------------------
# Write path
# ---------------------------------------------------------------------------
def write_step(
    state: MVState,
    slot_ids: jax.Array,   # i32[K] slots written this step (unique when masked)
    payloads: jax.Array,   # i32[K] new payload handles
    mask: jax.Array,       # bool[K]
    policy: str = "slrt",
    use_kernel: Optional[bool] = None,
    interpret: Optional[bool] = None,
    extra_pins: Optional[jax.Array] = None,
) -> Tuple[MVState, jax.Array, jax.Array]:
    """One bulk-synchronous update step: tick the clock, append versions,
    retire the overwritten ones into the ring (RT policies), and return the
    payload handles freed by any immediate policy action.

    Returns (state', freed_payloads, overflow[K]) — freed_payloads is i32[...]
    with EMPTY holes (callers recycle them, e.g. return KV pages to the free
    pool); overflow marks lanes whose append failed because the slot's slab
    was full — the engine must force a GC pass and retry those lanes (or, for
    EBR, provision larger slabs: this is precisely the paper's unbounded-EBR
    space pathology surfacing as a capacity requirement)."""
    assert policy in POLICIES, policy
    freed = jnp.full(slot_ids.shape, EMPTY, jnp.int32)
    if policy == "steam":
        # Steam compacts the list *when appending to it* (paper §2): sweep the
        # written slots before the append so reclaimed entries make room.
        state, freed = _sweep_slots(state, slot_ids, mask,
                                    use_kernel=use_kernel, interpret=interpret,
                                    extra_pins=extra_pins)
    now = state.now + 1
    store = state.store
    S, V = store.ts.shape

    # capture the overwritten (current) version per written slot BEFORE write
    rows_ts = store.ts[slot_ids]
    rows_succ = store.succ[slot_ids]
    is_cur = (rows_succ == TS_MAX) & (rows_ts != EMPTY)
    had_cur = is_cur.any(axis=1) & mask
    cur_v = jnp.argmax(is_cur, axis=1).astype(jnp.int32)
    retired_flat = slot_ids * V + cur_v
    retired_low = jnp.take_along_axis(rows_ts, cur_v[:, None], axis=1)[:, 0]

    store, overflow = pool.write(store, slot_ids, now, payloads, mask)
    state = state._replace(
        store=store,
        now=now,
        overflow_count=state.overflow_count + overflow.sum(),
    )

    if policy in ("dlrt", "slrt"):
        ring, dropped = rt.push(
            state.ring, retired_flat, retired_low, jnp.broadcast_to(now, retired_low.shape),
            had_cur & ~overflow,  # overflowed lanes closed nothing
        )
        state = state._replace(
            ring=ring, dropped_retires=state.dropped_retires + dropped.sum()
        )
    # ebr / sweep: nothing on the write path
    return state, freed, overflow


# ---------------------------------------------------------------------------
# Reader path
# ---------------------------------------------------------------------------
def begin_snapshot(state: MVState, lanes: jax.Array, mask: jax.Array) -> Tuple[MVState, jax.Array]:
    """Pin the current timestamp for the given reader lanes; returns their ts."""
    board = ann.announce(state.board, lanes, state.now, mask)
    return state._replace(board=board), jnp.broadcast_to(state.now, lanes.shape)


def end_snapshot(state: MVState, lanes: jax.Array, mask: jax.Array) -> MVState:
    return state._replace(board=ann.unannounce(state.board, lanes, mask))


def snapshot_read(
    state: MVState,
    slot_ids: jax.Array,
    t: jax.Array,
    use_kernel: Optional[bool] = None,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """rtx read: latest payload at-or-before t per slot (search(t)).

    ``use_kernel`` dispatches to the Pallas version_search kernel (interpret
    mode validates it on CPU); unset, the platform decides
    (:func:`repro.core.telemetry.resolve_kernel`): the kernel on a TPU, the
    lax masked-argmax path elsewhere."""
    use_kernel, interpret = resolve_kernel(use_kernel, interpret)
    if use_kernel:
        t_b = jnp.broadcast_to(jnp.asarray(t, jnp.int32), slot_ids.shape)
        return search_ops.search(
            state.store.ts, state.store.payload, slot_ids, t_b,
            use_kernel=True, interpret=interpret,
        )
    return pool.read_at(state.store, slot_ids, t)


def snapshot_gather(
    state: MVState,
    slot_ids: jax.Array,  # i32[B]
    t: jax.Array,         # i32[] or i32[B] pinned timestamp(s)
    values: jax.Array,    # i32[T, M] payload-indexed value rows
    use_kernel: Optional[bool] = None,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Fused rtx read: resolve search(t) per slot AND gather the value rows
    the resolved payloads index — one launch on the kernel path, one fused
    jit program on the lax path.  Returns ``(rows[B, M], payload[B],
    found[B])``; rows for not-found slots are EMPTY-filled.  This is the
    reader-lane primitive `mvkv.paged.snapshot_view` builds on (payload =
    page-table version index, values = page tables)."""
    t_b = jnp.broadcast_to(jnp.asarray(t, jnp.int32), slot_ids.shape)
    return search_ops.search_gather(
        state.store.ts, state.store.payload, values, slot_ids, t_b,
        use_kernel=use_kernel, interpret=interpret,
    )


def current_read(state: MVState, slot_ids: jax.Array) -> Tuple[jax.Array, jax.Array]:
    return pool.read_current(state.store, slot_ids)


# ---------------------------------------------------------------------------
# GC step
# ---------------------------------------------------------------------------
def _ann_scan(state: MVState, extra_pins: Optional[jax.Array]) -> jax.Array:
    """Sorted announcement snapshot for needed(), with any external pins
    appended as extra virtual lanes.

    ``extra_pins`` entries use the same vocabulary as board lanes: a real
    timestamp pins it, ``TS_MAX`` (or ``EMPTY``) pins nothing — ``needed()``
    treats both sentinels as inert, so padding is free.  The sharded stack
    passes the mesh-wide LWM here (DESIGN.md §13)."""
    if extra_pins is None:
        return ann.scan(state.board)
    extra = jnp.atleast_1d(jnp.asarray(extra_pins, jnp.int32))
    return sort_announcements(
        jnp.concatenate([state.board.slots, extra]))


def _ebr_bound(state: MVState, extra_pins: Optional[jax.Array]) -> jax.Array:
    """EBR epoch boundary: oldest local pin (or ``now``), clamped by the
    oldest external pin (``TS_MAX`` sentinels drop out of the min)."""
    bound = ann.oldest(state.board, state.now)
    if extra_pins is not None:
        extra = jnp.atleast_1d(jnp.asarray(extra_pins, jnp.int32))
        bound = jnp.minimum(bound, extra.min())
    return bound


def ckpt_kill_mask(state: MVState, ckpt_max: jax.Array,
                   extra_pins: Optional[jax.Array] = None) -> jax.Array:
    """bool[S, V]: turso's sole-survivor rule (SNIPPETS.md §1 rule 3,
    DESIGN.md §14).  An entry is evictable iff it is the *current* version
    (``succ == TS_MAX``), its slot's **only** live version (chain length 1 —
    older versions must drain through the normal policies first), it began
    at-or-before the durable checkpoint (``ts <= ckpt_max``: the slot has
    not been written since the checkpoint, so durable storage holds exactly
    this state), and it began before every pin in the system (``ts <
    bound``, the same LWM every policy honours).  ``ckpt_max`` is a traced
    i32 scalar; the ``EMPTY`` (-1) sentinel disables the rule entirely, so
    the mask composes under jit without retracing."""
    store = state.store
    ckpt = jnp.asarray(ckpt_max, jnp.int32)
    bound = _ebr_bound(state, extra_pins)
    valid = store.ts != EMPTY
    sole = (valid.sum(axis=1) == 1)[:, None]
    cur = (store.succ == TS_MAX) & valid
    return (cur & sole & (store.ts <= ckpt) & (store.ts < bound)
            & (ckpt >= 0))


def evict_checkpointed(
    state: MVState,
    ckpt_max: jax.Array,
    extra_pins: Optional[jax.Array] = None,
) -> Tuple[MVState, jax.Array, jax.Array]:
    """Free every entry :func:`ckpt_kill_mask` marks.  Returns
    (state', freed_payloads[S*V] with EMPTY holes, n_evicted).

    This is the checkpoint-coupled reclamation edge no policy can make on
    its own: current versions are by definition needed(A, t), so without a
    durable copy they are pinned forever.  With one, an idle-since-
    checkpoint slot's last version (and every page it pins, in the paged
    stack) becomes free — ``restore()`` resurrects it on demand.  Callers
    treat an evicted slot like a cold-miss: reading it finds no current
    version until the slot is restored or rewritten."""
    kill = ckpt_kill_mask(state, ckpt_max, extra_pins)
    freed = jnp.where(kill, state.store.payload, EMPTY).reshape(-1)
    n = kill.sum().astype(jnp.int32)
    return state._replace(store=pool.free_entries(state.store, kill)), freed, n


def gc_step(
    state: MVState,
    policy: str = "slrt",
    force: bool = False,
    flush_fraction: float = 0.5,
    use_kernel: Optional[bool] = None,
    interpret: Optional[bool] = None,
    extra_pins: Optional[jax.Array] = None,
    ckpt_max: Optional[jax.Array] = None,
) -> Tuple[MVState, jax.Array]:
    """Run the policy's collection pass.  Returns (state', freed_payloads).

    For RT policies the flush triggers when ring occupancy crosses
    ``flush_fraction`` (or unconditionally when ``force``) — the batched
    analogue of flushing every Θ(P log P) adds.  ``extra_pins`` (i32[...],
    ``TS_MAX`` = no pin) injects external announcements — e.g. the sharded
    stack's global LWM — honoured by every policy exactly like board lanes.
    ``ckpt_max`` (i32[], ``EMPTY`` = none) appends the checkpoint-coupled
    sole-survivor post-pass (:func:`evict_checkpointed`) after the policy's
    own collection — every policy inherits it unchanged (DESIGN.md §14)."""
    state, freed = _policy_gc_step(
        state, policy=policy, force=force, flush_fraction=flush_fraction,
        use_kernel=use_kernel, interpret=interpret, extra_pins=extra_pins)
    if ckpt_max is not None:
        state, freed_ck, _ = evict_checkpointed(state, ckpt_max, extra_pins)
        freed = jnp.concatenate([freed.reshape(-1), freed_ck])
    return state, freed


def _policy_gc_step(
    state: MVState,
    policy: str = "slrt",
    force: bool = False,
    flush_fraction: float = 0.5,
    use_kernel: Optional[bool] = None,
    interpret: Optional[bool] = None,
    extra_pins: Optional[jax.Array] = None,
) -> Tuple[MVState, jax.Array]:
    """The per-policy collection pass proper (no checkpoint post-pass)."""
    assert policy in POLICIES, policy
    S, V = state.store.ts.shape
    if policy == "ebr":
        bound = _ebr_bound(state, extra_pins)
        kill = pool.epoch_kill_mask(state.store, bound)
        freed = jnp.where(kill, state.store.payload, EMPTY).reshape(-1)
        return state._replace(store=pool.free_entries(state.store, kill)), freed

    if policy == "sweep":
        return _sweep_all_needed(state, use_kernel=use_kernel,
                                 interpret=interpret, extra_pins=extra_pins)

    if policy == "steam":
        # steam does its work on the write path; the periodic GC step is a
        # no-op (dusty corners live until the next append).  force=True is
        # the engine's shutdown/pressure escape hatch: one full sweep.
        if force:
            return _sweep_all_needed(state, use_kernel=use_kernel,
                                     interpret=interpret,
                                     extra_pins=extra_pins)
        return state, jnp.full((state.ring.capacity,), EMPTY, jnp.int32)

    # dlrt / slrt
    size = rt.ring_size(state.ring)
    thresh = int(state.ring.capacity * flush_fraction)
    do_flush = jnp.logical_or(size >= thresh, jnp.bool_(force))

    B = state.ring.capacity

    def _flush(st: MVState):
        A = _ann_scan(st, extra_pins)
        # slots implicated by the ring content (the paper: the lists whose
        # nodes the range tracker returned)
        occ = st.ring.idx != EMPTY
        touched = jnp.where(occ, st.ring.idx // V, 0)
        ring, store, freed = rt.flush(st.ring, st.store, A, st.now)
        st = st._replace(ring=ring, store=store)
        if policy == "slrt":
            # preemptive compaction of implicated slots (SSL compact): may
            # free entries never returned by the tracker.  freed handles can
            # repeat; payload recycling must be idempotent (bitmap set).
            st, freed2 = _sweep_slots(st, touched, occ,
                                      use_kernel=use_kernel,
                                      interpret=interpret,
                                      extra_pins=extra_pins)
            freed = jnp.concatenate([freed, freed2])
        else:
            freed = jnp.concatenate([freed, jnp.full((B * V,), EMPTY, jnp.int32)])
        return st, freed

    def _skip(st: MVState):
        return st, jnp.full((B + B * V,), EMPTY, jnp.int32)

    return jax.lax.cond(do_flush, _flush, _skip, state)


def _sweep_all_needed(
    state: MVState,
    use_kernel: Optional[bool] = None,
    interpret: Optional[bool] = None,
    extra_pins: Optional[jax.Array] = None,
) -> Tuple[MVState, jax.Array]:
    """Full-store needed-sweep: the fused compact primitive over every slab
    (mask all-true).  The Pallas kernel and the lax path share the same
    contract (one pass: splice + freed handles + count)."""
    S, V = state.store.ts.shape
    A = _ann_scan(state, extra_pins)
    new_ts, new_succ, new_pay, freed, _ = compact_ops.compact(
        state.store.ts, state.store.succ, state.store.payload,
        jnp.ones((S,), bool), A, state.now,
        use_kernel=use_kernel, interpret=interpret,
    )
    store = VersionStore(ts=new_ts, succ=new_succ, payload=new_pay)
    return state._replace(store=store), freed.reshape(-1)


def _sweep_slots(
    state: MVState,
    slot_ids: jax.Array,
    mask: jax.Array,
    use_kernel: Optional[bool] = None,
    interpret: Optional[bool] = None,
    extra_pins: Optional[jax.Array] = None,
) -> Tuple[MVState, jax.Array]:
    """needed-sweep restricted to the given slots (steam / slrt locality).

    ``use_kernel`` dispatches the gathered rows through the fused Pallas
    compaction kernel; otherwise the lax searchsorted form runs (the two are
    differentially tested in tests/mvgc/test_vstore.py); unset, the platform
    decides (:func:`repro.core.telemetry.resolve_kernel`)."""
    use_kernel, interpret = resolve_kernel(use_kernel, interpret)
    A = _ann_scan(state, extra_pins)
    rows_ts = state.store.ts[slot_ids]
    rows_succ = state.store.succ[slot_ids]
    rows_pay = state.store.payload[slot_ids]
    if use_kernel:
        new_ts, new_succ, new_pay, freed2d, _ = compact_ops.compact(
            rows_ts, rows_succ, rows_pay, mask, A, state.now,
            use_kernel=True, interpret=interpret,
        )
        freed = freed2d.reshape(-1)
    else:
        needed = needed_intervals(rows_ts, rows_succ, A, state.now)
        kill = ~needed & (rows_ts != EMPTY) & mask[:, None]
        freed = jnp.where(kill, rows_pay, EMPTY).reshape(-1)
        new_ts = jnp.where(kill, EMPTY, rows_ts)
        new_succ = jnp.where(kill, TS_MAX, rows_succ)
        new_pay = jnp.where(kill, EMPTY, rows_pay)
    store = VersionStore(
        ts=state.store.ts.at[slot_ids].set(new_ts, mode="drop"),
        succ=state.store.succ.at[slot_ids].set(new_succ, mode="drop"),
        payload=state.store.payload.at[slot_ids].set(new_pay, mode="drop"),
    )
    return state._replace(store=store), freed


# ---------------------------------------------------------------------------
# Pressure path (DESIGN.md §11): capacity gate -> hot slots -> reclaim
# ---------------------------------------------------------------------------
#: Deprecated alias: ``capacity_gate`` now returns the unified
#: :class:`repro.core.telemetry.PressureSignal` (DESIGN.md §13).  The old
#: per-layer fields map as level = max(slab frac, ring frac), live = total
#: live versions, capacity = S * V; ``under_pressure`` / ``deficit`` / ``live``
#: keep their names and meanings.
PressureReport = PressureSignal


def capacity_gate(
    state: MVState,
    slab_watermark: float = 0.75,
    ring_watermark: float = 0.5,
) -> PressureSignal:
    """Evaluate the slab- and ring-occupancy watermarks (turso's LWM rule:
    reclamation is *triggered by events* crossing a watermark, never by a
    timer alone).  ``deficit`` is the number of versions that must be freed
    to bring every slab under ``slab_watermark`` and the ring under
    ``ring_watermark`` — the quantity `reclaim_on_pressure` chases, mirroring
    the sim's ``ReclaimRequest.deficit``.  Returns the unified
    :class:`repro.core.telemetry.PressureSignal` (``level`` is the worse of
    the slab and ring occupancy fractions); all fields are traced values, so
    the gate composes under jit/shard_map."""
    S, V = state.store.ts.shape
    occ = (state.store.ts != EMPTY).sum(axis=1)
    slab_hi = max(1, int(slab_watermark * V))
    ring_hi = max(1, int(ring_watermark * state.ring.capacity))
    ring_size = rt.ring_size(state.ring)
    slab_over = jnp.maximum(occ - slab_hi, 0)
    deficit = slab_over.sum() + jnp.maximum(ring_size - ring_hi, 0)
    slab_frac = occ.max().astype(jnp.float32) / V
    ring_frac = ring_size.astype(jnp.float32) / state.ring.capacity
    return PressureSignal(
        level=jnp.maximum(slab_frac, ring_frac),
        under_pressure=(occ.max() > slab_hi) | (ring_size > ring_hi),
        deficit=deficit,
        live=occ.sum(),
        capacity=jnp.int32(S * V),
    )


def hot_slots(state: MVState, k: int) -> jax.Array:
    """Top-k slots by live-version occupancy — the deployable analogue of the
    sim's ``hot_keys`` resolution (the slots holding the most stale versions
    are where compaction pays first).  Returns i32[k], -1-padded for slots
    with <= 1 live version (nothing reclaimable: the current version stays)."""
    occ = (state.store.ts != EMPTY).sum(axis=1)
    vals, idx = jax.lax.top_k(occ, min(k, occ.shape[0]))
    return jnp.where(vals > 1, idx.astype(jnp.int32), -1)


def reclaim_on_pressure(
    state: MVState,
    hot_keys: jax.Array,  # i32[K] hot slot ids (-1 = inert lane), cf. hot_slots()
    deficit: jax.Array,   # i32[]  versions to free (capacity_gate().deficit)
    policy: str = "slrt",
    use_kernel: Optional[bool] = None,
    interpret: Optional[bool] = None,
    extra_pins: Optional[jax.Array] = None,
    ckpt_max: Optional[jax.Array] = None,
) -> Tuple[MVState, jax.Array, jax.Array]:
    """Synchronous pressure response with the optional checkpoint-coupled
    post-pass: the policy reclaim runs first (:func:`_policy_reclaim`), then
    — when ``ckpt_max`` is given (i32[], ``EMPTY`` = none) — the sole-
    survivor eviction frees idle-since-checkpoint slots the policy cannot
    touch (DESIGN.md §14).  Returns (state', freed_payloads, n_freed); the
    interface is otherwise exactly :func:`_policy_reclaim`'s."""
    live0 = live_versions(state)
    state, freed, _ = _policy_reclaim(
        state, hot_keys, deficit, policy=policy, use_kernel=use_kernel,
        interpret=interpret, extra_pins=extra_pins)
    if ckpt_max is not None:
        state, freed_ck, _ = evict_checkpointed(state, ckpt_max, extra_pins)
        freed = jnp.concatenate([freed.reshape(-1), freed_ck])
    return state, freed, live0 - live_versions(state)


def _policy_reclaim(
    state: MVState,
    hot_keys: jax.Array,
    deficit: jax.Array,
    policy: str = "slrt",
    use_kernel: Optional[bool] = None,
    interpret: Optional[bool] = None,
    extra_pins: Optional[jax.Array] = None,
) -> Tuple[MVState, jax.Array, jax.Array]:
    """Synchronous pressure response: run the policy's sweep over the hot
    slots first, spilling to the cold slabs only while the deficit is unmet —
    the jit-friendly port of the sim's ``SchemeBase.reclaim_on_pressure``
    (hot-first, then cold until ``freed >= deficit``), with the cold spill
    specialized through ``lax.cond``.

    Per policy (mirroring the sim's ``_reclaim`` overrides):

    * ``ebr``   — forced epoch turnover: free everything that closed before
                  the oldest pin; hot slots are irrelevant (EBR cannot target
                  a list — the paper's pathology, preserved deliberately).
    * ``steam`` — compact the hot slots' slabs, then cond-spill to a full
                  needed-sweep while the deficit is unmet.
    * ``dlrt``  — force-flush the retire ring (the tracker backlog *is* the
                  reclaimable set; exact entries only, like PDL.remove).
    * ``slrt``  — forced ring flush + implicated-slot sweep, then the hot
                  slots, then the cond cold spill (SSL compact's preemptive
                  splicing under pressure; the default).
    * ``sweep`` — the baseline: one full sweep, hot set ignored.

    Returns (state', freed_payloads, n_freed) — freed_payloads has EMPTY
    holes and may repeat handles (recycling must be idempotent); n_freed is
    the exact live-version delta."""
    assert policy in POLICIES, policy
    S, V = state.store.ts.shape
    live0 = live_versions(state)
    deficit = jnp.asarray(deficit, jnp.int32)

    if policy == "ebr":
        state, freed = gc_step(state, policy="ebr", extra_pins=extra_pins)
        return state, freed, live0 - live_versions(state)
    if policy == "sweep":
        state, freed = _sweep_all_needed(state, use_kernel=use_kernel,
                                         interpret=interpret,
                                         extra_pins=extra_pins)
        return state, freed, live0 - live_versions(state)
    if policy == "dlrt":
        state, freed = gc_step(state, policy="dlrt", force=True,
                               extra_pins=extra_pins)
        return state, freed, live0 - live_versions(state)

    # steam / slrt: hot-first, cold spill only while the deficit is unmet
    if policy == "slrt":
        state, freed_rt = gc_step(state, policy="slrt", force=True,
                                  use_kernel=use_kernel, interpret=interpret,
                                  extra_pins=extra_pins)
    else:
        freed_rt = jnp.full((0,), EMPTY, jnp.int32)
    state, freed_hot = _sweep_slots(state, jnp.maximum(hot_keys, 0),
                                    hot_keys >= 0, use_kernel=use_kernel,
                                    interpret=interpret,
                                    extra_pins=extra_pins)
    hot_met = (live0 - live_versions(state)) >= deficit

    def _cold(st: MVState):
        return _sweep_all_needed(st, use_kernel=use_kernel,
                                 interpret=interpret,
                                 extra_pins=extra_pins)

    def _skip(st: MVState):
        return st, jnp.full((S * V,), EMPTY, jnp.int32)

    state, freed_cold = jax.lax.cond(hot_met, _skip, _cold, state)
    freed = jnp.concatenate(
        [freed_rt.reshape(-1), freed_hot.reshape(-1), freed_cold.reshape(-1)])
    return state, freed, live0 - live_versions(state)


# ---------------------------------------------------------------------------
# Monitoring
# ---------------------------------------------------------------------------
def live_versions(state: MVState) -> jax.Array:
    return (state.store.ts != EMPTY).sum()


def space_report(state: MVState) -> dict:
    occ = pool.occupancy(state.store)
    return {
        "live_versions": int(live_versions(state)),
        "max_slot_occupancy": int(occ.max()),
        "ring_size": int(rt.ring_size(state.ring)),
        "overflows": int(state.overflow_count),
        "dropped_retires": int(state.dropped_retires),
    }
