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
from repro.core.telemetry import GCConfig, PressureSignal, span
from repro.dist.overlap import make_ring_all_reduce
from repro.dist.sharding import host_stacked_sharding
from repro.dist.straggler import StepWatchdog
from repro.mvkv import paged
from repro.serve.engine import PagedLoop


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
def _per_shard(fn, mesh, axis: str, donate: bool = True):
    """``jit`` of ``fn`` vmapped over the hosts each device holds, under
    ``fn``'s name.  A scalar argument is the same on every host and enters
    replicated; every other argument, and every result, is
    ``[H, ...]``-leading and split over ``axis`` by a ``shard_map``, so
    each device runs the single-host op on its own shards and nothing
    crosses devices; the GC kernels inside could not be split by XLA's
    partitioner anyway.  With ``donate`` the first two arguments (the state
    and the freed bits) are donated."""
    P = jax.sharding.PartitionSpec

    @functools.wraps(fn)
    def shards(*args):
        shared = tuple(getattr(a, "ndim", None) == 0 for a in args)
        return jax.shard_map(
            jax.vmap(fn, in_axes=tuple(None if s else 0 for s in shared)),
            mesh=mesh, in_specs=tuple(P() if s else P(axis) for s in shared),
            out_specs=P(axis), check_vma=False)(*args)

    return jax.jit(shards, donate_argnums=(0, 1) if donate else ())


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


class ShardedPagedKVEngine(PagedLoop):
    """`repro.serve.engine.PagedLoop` over ``hosts`` paged caches at once,
    with global-LWM reclamation.

    ``hosts`` logical shards, each owning ``num_seqs`` sequences and
    ``num_pages`` pool pages, stacked along a leading ``[H]`` dim and placed
    over ``mesh`` (default :func:`repro.launch.mesh.make_gc_mesh`).  On a
    mesh of several devices the stack is built sharded, ``hosts / devices``
    shards per device, and a host count the mesh does not divide raises; on
    a one-device mesh it stays unsharded and the LWM reduction is a plain
    ``min`` — the protocol is placement-independent.  The loop's entry
    points (`step`, `reset`, `fork`, `reclaim`, `checkpoint`, `restore`)
    take ``[H, ...]``-leading arguments and run the single-host programs on
    each shard (`_per_shard`), with the same donation, spans and one read
    per round; failed lanes come back ``[H, B]`` and counts are summed over
    hosts.  Sequence ids are host-local, so there is no fork DAG and no
    drain of freed pages.

    What this engine adds to the loop: the pins.  Before an op's first
    program and again each reclaim round it refreshes the global LWM
    (contributions -> staleness aging -> ring-min, `lwm_pins`) and threads
    it through the shard programs as ``extra_pins``, so reclamation on any
    shard respects every live host's pins.  Per-host :class:`StepWatchdog`
    instances supply the staleness budget when ``gc.stale_after_s`` is inf;
    ``virtual_ages_s`` lets tests and the dist bench inject deterministic
    announcement ages instead of wall clock.  Pins and snapshot reads are
    host-indexed (`pin`, `unpin`, `view_at`, `host_state`)."""

    def __init__(self, hosts: int, num_seqs: int, num_pages: int,
                 page_size: int, max_pages_per_seq: int, kv_heads: int,
                 head_dim: int, *, gc: Optional[GCConfig] = None,
                 mesh=None, dtype=jnp.float32):
        cfg = gc if gc is not None else GCConfig()
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
            st = jax.jit(build, out_shardings=shardings)()
            self._ring = jax.jit(make_ring_all_reduce(mesh, axis,
                                                      reduce="min"))
        else:
            st = build()
            self._ring = None
        super().__init__(st, None, cfg)

        def gc_gate(s):
            return paged.page_pressure(s, watermark=cfg.page_watermark)

        self._gate = self._program(gc_gate, donate=False)
        self.watchdogs: List[StepWatchdog] = [StepWatchdog()
                                              for _ in range(hosts)]
        # deterministic announcement ages for tests/benches (None = fresh)
        self.virtual_ages_s: Optional[np.ndarray] = None
        self.lwm_advances = 0
        self._last_lwm = -1
        self.forks = 0

    def _program(self, fn, donate: bool = True):
        return _per_shard(fn, self.mesh, self.mesh.axis_names[0], donate)

    def _pins(self) -> jax.Array:
        return self.lwm_pins()

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
        ring-min, read on the host with one fetch.  Returns the per-host
        ``extra_pins`` array ``i32[H, 1]`` (every host gets the same
        mesh-wide LWM) and accounts ``stale_lanes_aged`` /
        ``lwm_advances``."""
        contrib = lwm_contributions(self.st)
        aged, n_aged = age_out_stale(contrib, self.ages_s(), self.budget_s())
        lwm = global_lwm(aged, self._ring)
        n_aged, val = map(int, self._fetch((n_aged, lwm)))
        self.stats.stale_lanes_aged += n_aged
        # an "advance" is the LWM moving up from a real pin (TS_MAX is the
        # pin-free sentinel, not a position); decreases — a new pin arriving
        # — just retrack
        if 0 <= self._last_lwm < int(TS_MAX) and val > self._last_lwm:
            self.lwm_advances += 1
        self._last_lwm = val
        return jnp.broadcast_to(lwm, (self.hosts, 1))

    # -- the loop's ops that differ -------------------------------------------
    def fork(self, src_ids: jax.Array, dst_ids: jax.Array,
             mask: jax.Array) -> np.ndarray:
        """COW fork on every host (src and dst are host-local sequences),
        counted in ``forks``.  Returns failed[H, B]."""
        failed = self._fork_retry(src_ids, dst_ids, mask)
        self.forks += int((self._fetch(mask) & ~failed).sum())
        return failed

    def _extras(self) -> Dict[str, int]:
        return {"forks": self.forks, "lwm_advances": self.lwm_advances,
                "last_lwm": self._last_lwm}

    def _load_extras(self, tree, extra: Dict[str, int]) -> None:
        self.forks = int(extra.get("forks", 0))
        self.lwm_advances = int(extra.get("lwm_advances", 0))
        self._last_lwm = int(extra.get("last_lwm", -1))

    # -- host-local pins and snapshot reads ----------------------------------
    def pin(self, host: int, lane: int) -> int:
        """Pin ``host``'s current timestamp on its local board lane — the
        announcement never leaves the host; only the LWM reduction sees it.
        Returns the pinned timestamp."""
        with span("repro.snapshot.pin"):
            now = self.st.mv.now[host]
            slots = self.st.mv.board.slots.at[host, lane].set(now)
            board = self.st.mv.board._replace(slots=slots)
            self.st = self.st._replace(mv=self.st.mv._replace(board=board))
            return int(self._fetch(now))

    def unpin(self, host: int, lane: int) -> None:
        with span("repro.snapshot.unpin"):
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
        with span("repro.snapshot.read"):
            # the read needs the page tables and descriptors, not the pool
            local = jax.tree.map(lambda x: _host_slice(x, host),
                                 self.st._replace(k_pages=None,
                                                  v_pages=None))
            if seq_ids is None:
                seq_ids = jnp.arange(local.mv.store.ts.shape[0],
                                     dtype=jnp.int32)
            return self._read(local, seq_ids, np.int32(t))

    # -- telemetry ------------------------------------------------------------
    def pressure(self) -> PressureSignal:
        """The unified gate over all shards: ``PressureSignal`` with
        ``[H]`` vector fields (one entry per host)."""
        return self._gate(self.st)

    def space(self) -> Dict[str, int]:
        """Flat counters for BENCH_dist rows: the unified ReclaimStats
        vocabulary plus the dist-only fields and the host counters."""
        sig = self.pressure()
        rep = dict(self.stats.as_row())
        rep["hosts"] = self.hosts
        rep["free_pages"] = int(self.st.free.sum())
        rep["live_pages"] = self.st.free.size - rep["free_pages"]
        rep["page_pool"] = int(np.prod(self.st.free.shape))
        rep["under_pressure_hosts"] = int(sig.under_pressure.sum())
        rep["lwm"] = self._last_lwm
        rep["lwm_advances"] = self.lwm_advances
        rep["overflows"] = int(self.st.mv.overflow_count.sum())
        rep["dropped_retires"] = int(self.st.mv.dropped_retires.sum())
        rep["forks"] = self.forks
        rep.update(self.counters.as_row())
        return rep
