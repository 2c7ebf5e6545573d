"""Multiversioned paged KV cache: COW page tables over a shared page pool.

The missing piece between the descriptor store (`core.mvgc.vstore`) and the
attention kernels (`kernels.decode_attention`): KV lives in fixed-size pages
in a pool; each sequence's **page table is a versioned object** — decode
steps that fill a page (or fork a sequence) write a *new page-table version*;
snapshot readers resolve their pinned timestamp to a page-table version via
``vstore.snapshot_read`` and attend over exactly the pages visible then.
A page is recycled only when no reachable page-table version references it —
computed with the same reachability sweep the paper's GC uses.

Everything is fixed-shape and jit-friendly: page tables live in a dense
``tables[MAX_VERSIONS, MP]`` array indexed by the descriptor payloads; the
free pool is a bitmap with ranked-hole allocation (same trick as the retire
ring).
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.mvgc import vstore
from repro.core.mvgc.pool import EMPTY
from repro.core.telemetry import GCConfig, PressureSignal, resolve_gc_config

NO_PAGE = jnp.int32(-1)


class PagedKV(NamedTuple):
    k_pages: jax.Array     # [N, PS, Hkv, D] page pool
    v_pages: jax.Array     # [N, PS, Hkv, D]
    free: jax.Array        # bool[N]  (True = free)
    tables: jax.Array      # i32[MAX_VER, MP] page-table versions (NO_PAGE pad)
    table_free: jax.Array  # bool[MAX_VER] free page-table slots
    lengths: jax.Array     # i32[MAX_VER] tokens covered by each table version
    mv: vstore.MVState     # descriptor store: slot=sequence, payload=table idx

    @property
    def page_size(self) -> int:
        return self.k_pages.shape[1]

    @property
    def max_pages(self) -> int:
        return self.tables.shape[1]


def make_paged_kv(num_seqs: int, num_pages: int, page_size: int,
                  max_pages_per_seq: int, kv_heads: int, head_dim: int,
                  versions_per_seq: Optional[int] = None,
                  reader_lanes: Optional[int] = None,
                  ring_capacity: Optional[int] = None, dtype=jnp.bfloat16,
                  *, gc: Optional[GCConfig] = None) -> PagedKV:
    """Build an empty paged-KV state.  GC sizing comes from ``gc``
    (:class:`repro.core.telemetry.GCConfig`); the old ``versions_per_seq`` /
    ``reader_lanes`` / ``ring_capacity`` kwargs still work but are deprecated
    (DESIGN.md §13 migration table)."""
    cfg = resolve_gc_config(gc, "make_paged_kv",
                            versions_per_slot=versions_per_seq,
                            reader_lanes=reader_lanes,
                            ring_capacity=ring_capacity)
    max_ver = num_seqs * cfg.versions_per_slot
    # Reclamation is pressure-driven (no per-append cadence GC), so the
    # retire ring must absorb every close between two pressure flushes —
    # up to one per slab entry plus the in-flight step.  An undersized ring
    # drops retire records (`dropped_retires`), which the DLRT policy can
    # never recover (its reclaim walks only the ring); size it to the slab
    # by default and let callers shrink it deliberately.
    ring = cfg.ring_capacity if cfg.ring_capacity > 0 else max(16, 2 * max_ver)
    return PagedKV(
        k_pages=jnp.zeros((num_pages, page_size, kv_heads, head_dim), dtype),
        v_pages=jnp.zeros((num_pages, page_size, kv_heads, head_dim), dtype),
        free=jnp.ones((num_pages,), bool),
        tables=jnp.full((max_ver, max_pages_per_seq), NO_PAGE, jnp.int32),
        table_free=jnp.ones((max_ver,), bool),
        lengths=jnp.zeros((max_ver,), jnp.int32),
        mv=vstore.make_state(num_seqs, cfg.versions_per_slot,
                             cfg.reader_lanes, ring_capacity=ring),
    )


def _alloc(free: jax.Array, want: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Rank-match allocation: want[i] lanes get the i-th free slot.
    Returns (new_free, slot_ids[K] (=len(want) with -1 fails), ok[K])."""
    n = free.shape[0]
    pos = jnp.sort(jnp.where(free, jnp.arange(n, dtype=jnp.int32), n))
    rank = jnp.cumsum(want.astype(jnp.int32)) - 1
    ok = want & (rank < free.sum())
    slots = jnp.where(ok, pos[jnp.minimum(rank, n - 1)], -1)
    new_free = free.at[jnp.where(ok, slots, n)].set(False, mode="drop")
    return new_free, slots, ok


def append_tokens(
    st: PagedKV,
    seq_ids: jax.Array,    # i32[B] sequences receiving one token each
    k_new: jax.Array,      # [B, Hkv, D]
    v_new: jax.Array,      # [B, Hkv, D]
    mask: jax.Array,       # bool[B]
    gc_policy: str = "slrt",
    use_kernel: Optional[bool] = None,
    interpret: Optional[bool] = None,
    extra_pins: Optional[jax.Array] = None,
) -> Tuple[PagedKV, jax.Array]:
    """One decode step: write each sequence's token into its current page,
    allocating a fresh page at page boundaries, and commit a **new page-table
    version for every appended token** (COW).  Returns (state', failed[B]).

    Versioning every append (not just page boundaries) is what makes the rtx
    contract hold: the visible *length* lives on the table version, so a
    pinned snapshot's length can never grow underneath it.  Only the partial
    last page's slot at ``off`` is written in place — safe because every live
    table version's length is <= ``off``, so no reader can see the cell until
    a later version publishes it.  A lane fails (returned mask True) when the
    page pool, the table-slot pool, or the descriptor slab cannot take the
    append — the caller reclaims under pressure and retries
    (`reclaim_on_pressure`), the paper's abort => reclaim => retry loop."""
    PS = st.page_size
    MP = st.max_pages
    B = seq_ids.shape[0]
    MAX_VER = st.tables.shape[0]

    cur_tbl, has = vstore.current_read(st.mv, seq_ids)        # i32[B]
    cur_tbl_safe = jnp.where(has, cur_tbl, 0)
    lengths = jnp.where(has, st.lengths[cur_tbl_safe], 0)     # i32[B]
    page_idx = lengths // PS
    off = lengths % PS
    needs_page = (off == 0) & mask                             # new page needed

    # allocate pages for boundary lanes
    new_free, pages, got_page = _alloc(st.free, needs_page)
    page_of = jnp.where(
        needs_page, pages,
        st.tables[cur_tbl_safe, jnp.minimum(page_idx, MP - 1)])
    ok = mask & jnp.where(needs_page, got_page, page_of >= 0) & (page_idx < MP)

    # write the token into (page_of, off)
    dest_page = jnp.where(ok, page_of, st.k_pages.shape[0])   # OOB = drop
    k_pages = st.k_pages.at[dest_page, off].set(
        k_new.astype(st.k_pages.dtype), mode="drop")
    v_pages = st.v_pages.at[dest_page, off].set(
        v_new.astype(st.v_pages.dtype), mode="drop")

    # every ok lane commits a NEW page-table version (COW row copy; fresh
    # sequences start from an all-NO_PAGE row, not slot 0's content)
    tf, tslots, got_tbl = _alloc(st.table_free, ok)
    commit = ok & got_tbl
    old_rows = jnp.where(has[:, None], st.tables[cur_tbl_safe], NO_PAGE)
    pcol = jnp.minimum(page_idx, MP - 1)
    new_rows = old_rows.at[jnp.arange(B), pcol].set(
        jnp.where(needs_page & commit, page_of, old_rows[jnp.arange(B), pcol]))
    tdest = jnp.where(commit, tslots, MAX_VER)
    tables = st.tables.at[tdest].set(new_rows, mode="drop")

    # the new table version owns the advanced length
    lengths_arr = st.lengths.at[tdest].set(lengths + 1, mode="drop")

    # descriptor write: one new version (payload = table slot) per commit
    # lane.  No cadence GC here: the serving path reclaims only under
    # pressure (`reclaim_on_pressure`, the turso LWM rule) — paying a full
    # collection pass per decoded token is exactly the practical cost the
    # paper's schemes avoid.  Steam is the exception by design: its sweep
    # rides inside `write_step` itself (compact-on-write), so `freed` below
    # is nonempty for steam even without a pressure event.
    mv, freed, ovf = vstore.write_step(
        st.mv, seq_ids, tslots, commit, policy=gc_policy,
        use_kernel=use_kernel, interpret=interpret, extra_pins=extra_pins)
    freed_all = freed.reshape(-1)

    # a lane whose descriptor append overflowed must hand its table slot back
    # (otherwise retries leak unreferenced-but-allocated slots)
    table_free = tf.at[
        jnp.where(commit & ovf, tslots, MAX_VER)
    ].set(True, mode="drop")

    # recycle table slots whose descriptor versions were collected, then
    # recycle pages unreachable from any live table version
    table_free = table_free.at[
        jnp.where(freed_all != EMPTY, freed_all, MAX_VER)
    ].set(True, mode="drop")
    free_pages = _sweep_unreferenced(tables, table_free, new_free)

    st2 = PagedKV(k_pages, v_pages, free_pages, tables, table_free,
                  lengths_arr, mv)
    return st2, mask & ~(commit & ~ovf)


def reset_sequence(
    st: PagedKV,
    seq_ids: jax.Array,    # i32[B] sequence slots being recycled
    mask: jax.Array,       # bool[B]
    gc_policy: str = "slrt",
    use_kernel: Optional[bool] = None,
    interpret: Optional[bool] = None,
    extra_pins: Optional[jax.Array] = None,
) -> Tuple[PagedKV, jax.Array]:
    """Sequence completion: commit a new *empty* page-table version (zero
    pages, zero length) so the slot can serve the next request.  Returns
    (state', failed[B]).  The old pages are **not** freed here — they stay
    pinned by the stale table versions until the GC policy collects them
    (and by any snapshot still reading the finished sequence); this is the
    dominant page-release path of a continuous-decode storm, and exactly why
    pool pressure must drive descriptor compaction."""
    MAX_VER = st.tables.shape[0]
    B = seq_ids.shape[0]
    tf, tslots, got = _alloc(st.table_free, mask)
    ok = mask & got
    tdest = jnp.where(ok, tslots, MAX_VER)
    tables = st.tables.at[tdest].set(
        jnp.full((B, st.max_pages), NO_PAGE, jnp.int32), mode="drop")
    lengths_arr = st.lengths.at[tdest].set(0, mode="drop")
    mv, freed, ovf = vstore.write_step(
        st.mv, seq_ids, tslots, ok, policy=gc_policy,
        use_kernel=use_kernel, interpret=interpret, extra_pins=extra_pins)
    table_free = tf.at[jnp.where(ok & ovf, tslots, MAX_VER)].set(
        True, mode="drop")
    table_free = table_free.at[
        jnp.where(freed != EMPTY, freed, MAX_VER)
    ].set(True, mode="drop")
    free_pages = _sweep_unreferenced(tables, table_free, st.free)
    st2 = PagedKV(st.k_pages, st.v_pages, free_pages, tables, table_free,
                  lengths_arr, mv)
    return st2, mask & ~(ok & ~ovf)


def fork_sequence(
    st: PagedKV,
    src_ids: jax.Array,    # i32[B] parent sequences
    dst_ids: jax.Array,    # i32[B] child sequence slots
    mask: jax.Array,       # bool[B]
    gc_policy: str = "slrt",
    use_kernel: Optional[bool] = None,
    interpret: Optional[bool] = None,
    extra_pins: Optional[jax.Array] = None,
    copy_pages: bool = False,
) -> Tuple[PagedKV, jax.Array]:
    """COW fork: the child's first page-table version *shares every page*
    with the parent's current version, except a *partial last page*, which is
    copied — both sides append in place at the tail, so a shared partial page
    would let the child clobber the parent's next token (and vice versa).
    Full pages stay shared: they are immutable once published.  Returns
    (state', failed[B]).  Shared pages stay live until no reachable table
    version of *either* sequence references them — the reachability sweep
    needs no refcounts for this, exactly the property the paper's GC
    exploits.

    ``copy_pages=True`` (static) is the **eager-copy control**: the child
    deep-copies *every* page the parent references instead of sharing the
    full ones — the fork semantics of a non-COW cache.  Nothing downstream
    changes (same table-version commit, same sweep); the only difference is
    page demand, which is exactly what ``benchmarks/fork_bench.py`` measures
    COW against (DESIGN.md §14)."""
    MAX_VER = st.tables.shape[0]
    PS = st.page_size
    MP = st.max_pages
    B = src_ids.shape[0]
    N_PAGES = st.k_pages.shape[0]
    src_tbl, has = vstore.current_read(st.mv, src_ids)
    src_safe = jnp.where(has, src_tbl, 0)
    src_len = jnp.where(has, st.lengths[src_safe], 0)
    off = src_len % PS
    pcol = jnp.minimum(src_len // PS, MP - 1)

    if copy_pages:
        # eager control: allocate + copy every page the parent covers
        n_used = (src_len + PS - 1) // PS
        want2d = ((jnp.arange(MP, dtype=jnp.int32)[None, :] < n_used[:, None])
                  & (mask & has)[:, None])
        free2, cflat, got = _alloc(st.free, want2d.reshape(-1))
        got2d = got.reshape(B, MP)
        lane_ok = mask & has & (got2d | ~want2d).all(axis=1)
        tf, tslots, got_t = _alloc(st.table_free, lane_ok)
        ok = lane_ok & got_t
        # hand back pages allocated for lanes that didn't fully make it
        # (partial page allocation at pool exhaustion, or no table slot)
        giveback = got & ~jnp.repeat(ok, MP)
        free2 = free2.at[jnp.where(giveback, cflat, N_PAGES)].set(
            True, mode="drop")
        do_copy2d = want2d & ok[:, None]
        rows = jnp.where(do_copy2d, cflat.reshape(B, MP), NO_PAGE)
        src_flat = jnp.maximum(st.tables[src_safe], 0).reshape(-1)
        cdest = jnp.where(do_copy2d.reshape(-1), cflat, N_PAGES)
        k_pages = st.k_pages.at[cdest].set(st.k_pages[src_flat], mode="drop")
        v_pages = st.v_pages.at[cdest].set(st.v_pages[src_flat], mode="drop")
    else:
        needs_copy = mask & has & (off > 0)

        free2, cpages, got_page = _alloc(st.free, needs_copy)
        ok0 = mask & has & (~needs_copy | got_page)
        tf, tslots, got = _alloc(st.table_free, ok0)
        ok = ok0 & got

        rows = jnp.where(ok[:, None], st.tables[src_safe], NO_PAGE)
        do_copy = needs_copy & ok
        rows = rows.at[jnp.arange(B), pcol].set(
            jnp.where(do_copy, cpages, rows[jnp.arange(B), pcol]))
        src_page = st.tables[src_safe, pcol]
        src_page_safe = jnp.maximum(src_page, 0)
        cdest = jnp.where(do_copy, cpages, N_PAGES)
        k_pages = st.k_pages.at[cdest].set(st.k_pages[src_page_safe],
                                           mode="drop")
        v_pages = st.v_pages.at[cdest].set(st.v_pages[src_page_safe],
                                           mode="drop")

    tdest = jnp.where(ok, tslots, MAX_VER)
    tables = st.tables.at[tdest].set(rows, mode="drop")
    lengths_arr = st.lengths.at[tdest].set(src_len, mode="drop")

    mv, freed, ovf = vstore.write_step(
        st.mv, dst_ids, tslots, ok, policy=gc_policy,
        use_kernel=use_kernel, interpret=interpret, extra_pins=extra_pins)
    table_free = tf.at[jnp.where(ok & ovf, tslots, MAX_VER)].set(
        True, mode="drop")
    table_free = table_free.at[
        jnp.where(freed != EMPTY, freed, MAX_VER)
    ].set(True, mode="drop")
    free_pages = _sweep_unreferenced(tables, table_free, free2)
    st2 = PagedKV(k_pages, v_pages, free_pages, tables, table_free,
                  lengths_arr, mv)
    return st2, mask & ~(ok & ~ovf)


# ---------------------------------------------------------------------------
# Pressure path (DESIGN.md §11): pool watermark -> hot sequences -> reclaim
# ---------------------------------------------------------------------------
#: Deprecated alias: ``page_pressure`` now returns the unified
#: :class:`repro.core.telemetry.PressureSignal` (DESIGN.md §13).  The old
#: fields survive as properties: ``free_pages`` = capacity - live,
#: ``free_frac`` = 1 - level.
PagePressure = PressureSignal


def page_pressure(st: PagedKV, watermark: float = 0.25) -> PressureSignal:
    """Free-bitmap popcount under the watermark = pool pressure.  The deficit
    is measured in pages; `reclaim_on_pressure` chases it by freeing stale
    descriptor versions (each stale table version pins >= 0 pages).  Returns
    the unified :class:`repro.core.telemetry.PressureSignal` (``level`` is
    the occupied fraction of the pool)."""
    n = st.free.shape[0]
    lo = max(1, int(watermark * n))
    free = st.free.sum()
    return PressureSignal(
        level=1.0 - free.astype(jnp.float32) / n,
        under_pressure=free < lo,
        deficit=jnp.maximum(lo - free, 0),
        live=(jnp.int32(n) - free).astype(jnp.int32),
        capacity=jnp.int32(n),
    )


def hot_sequences(st: PagedKV, k: int) -> jax.Array:
    """Sequences holding the most live descriptor versions — the hot set for
    pressure-driven compaction (most stale table versions = most pinned-but-
    dead pages).  Delegates to `vstore.hot_slots` (slot = sequence)."""
    return vstore.hot_slots(st.mv, k)


def reclaim_on_pressure(
    st: PagedKV,
    hot_keys: jax.Array,   # i32[K] hot sequence ids (-1 = inert lane)
    deficit: jax.Array,    # i32[] pages wanted (page_pressure().deficit)
    gc_policy: str = "slrt",
    use_kernel: Optional[bool] = None,
    interpret: Optional[bool] = None,
    extra_pins: Optional[jax.Array] = None,
    ckpt_max: Optional[jax.Array] = None,
) -> Tuple[PagedKV, jax.Array]:
    """Synchronous page reclamation: hot-sequence-first descriptor compaction
    (`vstore.reclaim_on_pressure`), recycle the table slots whose descriptor
    versions were collected, then the reachability sweep recycles every page
    no live table version references.  Returns (state', pages_freed).

    The version deficit is the page deficit: every freed descriptor version
    releases exactly one table version which un-pins up to MP pages, so
    chasing ``deficit`` versions is a conservative target for ``deficit``
    pages.

    ``ckpt_max`` (optional, DESIGN.md §14) additionally evicts idle
    sole-survivor sequences whose current version is durably checkpointed —
    pages no policy can otherwise touch, because current versions are always
    needed."""
    MAX_VER = st.tables.shape[0]
    mv, freed, _ = vstore.reclaim_on_pressure(
        st.mv, hot_keys, deficit, policy=gc_policy,
        use_kernel=use_kernel, interpret=interpret, extra_pins=extra_pins,
        ckpt_max=ckpt_max)
    table_free = st.table_free.at[
        jnp.where(freed != EMPTY, freed, MAX_VER)
    ].set(True, mode="drop")
    free_pages = _sweep_unreferenced(st.tables, table_free, st.free)
    pages_freed = free_pages.sum() - st.free.sum()
    return (
        st._replace(mv=mv, table_free=table_free, free=free_pages),
        pages_freed,
    )


def evict_checkpointed(
    st: PagedKV,
    ckpt_max: jax.Array,   # i32[] highest durably checkpointed ts (EMPTY=none)
    extra_pins: Optional[jax.Array] = None,
) -> Tuple[PagedKV, jax.Array, jax.Array]:
    """turso's sole-survivor rule at page granularity (DESIGN.md §14): evict
    every sequence whose *only* version is durably checkpointed
    (``ts <= ckpt_max``) and unpinned, recycle its table slot, and sweep the
    pages it held.  Returns (state', pages_freed, versions_evicted).

    This frees pages **no GC policy can reach** — current versions are always
    needed — which is exactly what makes checkpoint coupling a new
    reclamation edge rather than a faster policy.  An evicted sequence reads
    as having no current version until ``restore()``d or rewritten; callers
    must only advertise a checkpoint they can actually restore from."""
    MAX_VER = st.tables.shape[0]
    mv, freed, n_ev = vstore.evict_checkpointed(st.mv, ckpt_max, extra_pins)
    table_free = st.table_free.at[
        jnp.where(freed != EMPTY, freed, MAX_VER)
    ].set(True, mode="drop")
    free_pages = _sweep_unreferenced(st.tables, table_free, st.free)
    pages_freed = free_pages.sum() - st.free.sum()
    return (
        st._replace(mv=mv, table_free=table_free, free=free_pages),
        pages_freed,
        n_ev,
    )


def _sweep_unreferenced(tables, table_free, page_free) -> jax.Array:
    """A page is live iff referenced by any live table version — the paper's
    reachability sweep at page granularity (one scatter, no traversal)."""
    n_pages = page_free.shape[0]
    live_refs = jnp.where(table_free[:, None], NO_PAGE, tables).reshape(-1)
    referenced = jnp.zeros((n_pages,), bool).at[
        jnp.where(live_refs >= 0, live_refs, n_pages)
    ].set(True, mode="drop")
    return ~referenced


def snapshot_view(st: PagedKV, seq_ids: jax.Array, t: jax.Array,
                  use_kernel: Optional[bool] = None,
                  interpret: Optional[bool] = None,
                  ) -> Tuple[jax.Array, jax.Array]:
    """Resolve a pinned timestamp to (page_table[B, MP], lengths[B]) — the
    rtx read: feed straight into kernels.decode_attention.paged_decode.

    Built on the fused search+gather primitive: the visible length rides
    along as an extra value column, so one launch resolves search(t) AND
    fetches each hit's page-table row + length (no search-then-index)."""
    MP = st.max_pages
    values = jnp.concatenate([st.tables, st.lengths[:, None]], axis=1)
    rows, _, found = vstore.snapshot_gather(
        st.mv, seq_ids, t, values, use_kernel=use_kernel, interpret=interpret)
    # not-found rows come back EMPTY-filled (== NO_PAGE for the table part);
    # the visible length is capped at the snapshot's table version
    tables = rows[:, :MP]
    lengths = jnp.where(found, rows[:, MP], 0)
    return tables, lengths


def snapshot_pin(mv: vstore.MVState, lane: jax.Array
                 ) -> Tuple[vstore.MVState, jax.Array]:
    """Pin reader ``lane`` at the store's clock: (mv', its timestamp).  It
    takes and returns the descriptor store alone, so that as a program it
    copies neither the page pool nor a model's weights."""
    mv, ts = vstore.begin_snapshot(mv, jnp.atleast_1d(lane),
                                   jnp.array([True]))
    return mv, ts[0]


def snapshot_unpin(mv: vstore.MVState, lane: jax.Array) -> vstore.MVState:
    """Release reader ``lane``'s pin (descriptor store in, store out)."""
    return vstore.end_snapshot(mv, jnp.atleast_1d(lane), jnp.array([True]))


def begin_snapshot(st: PagedKV, lane: jax.Array) -> Tuple[PagedKV, jax.Array]:
    mv, ts = snapshot_pin(st.mv, lane)
    return st._replace(mv=mv), ts


def end_snapshot(st: PagedKV, lane: jax.Array) -> PagedKV:
    return st._replace(mv=snapshot_unpin(st.mv, lane))


def live_pages(st: PagedKV) -> jax.Array:
    return (~st.free).sum()
