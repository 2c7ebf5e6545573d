"""One pressure/telemetry vocabulary for the whole MVGC stack (DESIGN.md §13).

Before this module the repo spoke three disjoint dialects for the same
signal: the sim's ``ContentionManager.pressure()`` (a 0..1 float),
``vstore.PressureReport`` (slab/ring watermark scalars) and
``mvkv.paged.PagePressure`` (free-bitmap watermark scalars), with the serve
engines flattening either into ad-hoc counter dicts.  The sharded multi-host
stack (``repro.dist.mvgc``) would have added a fourth.  Everything now
produces/consumes two types:

* :class:`PressureSignal` — the instantaneous *how full are we* gate output.
  A NamedTuple of traced-friendly scalars (or ``[H]`` vectors on a stacked
  multi-host state), so it composes under jit / shard_map / vmap exactly
  like the per-layer reports it replaces.  ``vstore.capacity_gate``,
  ``mvkv.paged.page_pressure`` and ``ContentionManager.pressure_signal``
  all return it; the old names (``PressureReport``, ``PagePressure``,
  ``pressure()``) remain as thin deprecated aliases for one release.
* :class:`ReclaimStats` — the host-side *what did reclamation do about it*
  accounting: a mutable counter bundle whose :meth:`ReclaimStats.as_row`
  emits the schema-v4 BENCH field names (``pressure_events``,
  ``reclaims_triggered``, ``pages_reclaimed``, ...), so BENCH payloads and
  existing tests stay valid while the engines share one implementation.
* :class:`EngineCounters`, :func:`span` and :func:`fetch` — what a serving
  engine's host loop does, on the profiler's clock: ``repro.<layer>.<what>``
  spans around each layer's calls (nothing is written unless a trace is
  being taken), and one counted way to read device values on the host,
  so every blocking device-to-host read is a ``repro.engine.sync`` span.

:class:`GCConfig` collapses the GC/pressure kwarg sprawl that had crept into
``make_paged_kv`` / ``PagedKVEngine`` / ``RunConfig`` (policy, slab depth,
reader lanes, ring capacity, kernel dispatch, watermarks, reclaim rounds)
into one frozen dataclass threaded through the engines, the vstore and the
benchmarks; the old kwargs emit ``DeprecationWarning`` for one release.
"""
from __future__ import annotations

import dataclasses
import math
import warnings
from typing import Any, Dict, NamedTuple, Optional, Tuple


class PressureSignal(NamedTuple):
    """Unified capacity-gate output (DESIGN.md §13).

    All fields are traced-friendly scalars — or per-host vectors when the
    producer runs over a host-stacked state — so the signal flows through
    ``lax.cond`` triggers and shard_map boundaries unchanged.  Producers map
    their native vocabulary onto it:

    ======================  ======================================  =========
    field                   vstore (descriptor slabs)               paged pool
    ======================  ======================================  =========
    ``level``               max(slab frac, ring frac)               1 - free frac
    ``under_pressure``      either watermark crossed                below watermark
    ``deficit``             versions to free                        pages to free
    ``live``                live versions                           live pages
    ``capacity``            slots x versions_per_slot               pool pages
    ======================  ======================================  =========
    """

    level: Any            # f32 0..1 resource-fullness (1.0 = exhausted)
    under_pressure: Any   # bool: a watermark is crossed — reclaim now
    deficit: Any          # i32 units (versions/pages) to free to clear it
    live: Any             # i32 currently-live units
    capacity: Any         # i32 total units the resource can hold

    @property
    def free_frac(self):
        """Deprecated ``PagePressure.free_frac`` alias (= 1 - level)."""
        return 1.0 - self.level

    @property
    def free_pages(self):
        """Deprecated ``PagePressure.free_pages`` alias (= capacity - live)."""
        return self.capacity - self.live


@dataclasses.dataclass
class ReclaimStats:
    """Host-side reclamation accounting shared by every engine.

    ``unit`` names what ``reclaimed``/``peak_live`` count (``"pages"`` for
    the paged engines, ``"versions"`` for descriptor-only ones).  The field
    names are engine-neutral; :meth:`as_row` maps them back onto the
    schema-v4 BENCH vocabulary (``pages_reclaimed``, ``peak_pages``, ...)
    so committed payloads and their checkers keep working unchanged.
    """

    unit: str = "pages"
    pressure_events: int = 0        # gate triggers (failed op or watermark)
    reclaims_triggered: int = 0     # synchronous reclaim passes actually run
    reclaimed: int = 0              # units returned to the free pool
    give_ups: int = 0               # lanes abandoned after max reclaim rounds
    peak_live: int = 0              # max live units ever observed
    peak_live_post_reclaim: int = 0  # max live units right after a reclaim
    stale_lanes_aged: int = 0       # dist: stale host announcements aged out
    ckpt_evictions: int = 0         # sole-survivor evictions (DESIGN.md §14)
    ckpt_freed: int = 0             # units freed by checkpoint eviction alone

    def note_event(self) -> None:
        """One pressure event (a failed append/fork/reset or a watermark
        crossing) — the trigger, not the response."""
        self.pressure_events += 1

    def note_reclaim(self, freed: int, live_after: int) -> None:
        """One synchronous reclaim pass that freed ``freed`` units, leaving
        ``live_after`` live (feeds the post-reclaim peak)."""
        self.reclaims_triggered += 1
        self.reclaimed += max(0, int(freed))
        self.peak_live_post_reclaim = max(self.peak_live_post_reclaim,
                                          int(live_after))

    def note_ckpt_eviction(self, evicted: int, freed: int) -> None:
        """One checkpoint-eviction pass: ``evicted`` sole-survivor versions
        dropped because durable storage has them, freeing ``freed`` units no
        GC policy could otherwise reclaim (DESIGN.md §14)."""
        self.ckpt_evictions += max(0, int(evicted))
        self.ckpt_freed += max(0, int(freed))

    def note_live(self, live: int) -> None:
        """Track the all-time live peak."""
        self.peak_live = max(self.peak_live, int(live))

    def as_row(self) -> Dict[str, int]:
        """The schema-v4 BENCH serve-field names (``units['serve_pressure']``)."""
        return {
            "pressure_events": self.pressure_events,
            "reclaims_triggered": self.reclaims_triggered,
            f"{self.unit}_reclaimed": self.reclaimed,
            "give_ups": self.give_ups,
            f"peak_{self.unit}": self.peak_live,
            f"peak_{self.unit}_post_reclaim": self.peak_live_post_reclaim,
            "stale_lanes_aged": self.stale_lanes_aged,
            "ckpt_evictions": self.ckpt_evictions,
            f"ckpt_{self.unit}_freed": self.ckpt_freed,
        }


@dataclasses.dataclass
class EngineCounters:
    """Host-side counts of a serving engine's loop: ``steps`` taken, and
    ``host_syncs``, the blocking device-to-host reads (each one `fetch`)."""

    steps: int = 0
    host_syncs: int = 0

    def as_row(self) -> Dict[str, int]:
        return dataclasses.asdict(self)


def span(name: str, **args: Any):
    """A host span ``name`` in the profiler's trace, with ``args`` as its
    metadata.  It lands on the host plane, on the clock of the device's
    events, so device idle time can be charged to the span open around it;
    when no trace is being taken it records nothing."""
    import jax

    return jax.profiler.TraceAnnotation(name, **args)


def fetch(tree: Any, counters: EngineCounters) -> Any:
    """The one way an engine reads device values on the host: a single
    ``jax.device_get`` of ``tree`` (one blocking round trip for all its
    leaves) inside a ``repro.engine.sync`` span, counted in
    ``counters.host_syncs``.  Returns the tree with numpy leaves."""
    import jax

    counters.host_syncs += 1
    with span("repro.engine.sync"):
        return jax.device_get(tree)


@dataclasses.dataclass(frozen=True)
class GCConfig:
    """Every GC/pressure knob in one place (DESIGN.md §13).

    Threaded through ``vstore.make_state`` / ``mvkv.paged.make_paged_kv`` /
    ``serve.engine.PagedKVEngine`` / ``configs.base.RunConfig`` and the
    benchmarks, replacing the per-call kwarg sprawl (``ring_capacity``,
    ``use_kernel``, ``kernel_interpret``, pool sizes, watermarks).  The old
    kwargs still work for one release but emit ``DeprecationWarning``.
    """

    policy: str = "slrt"            # ebr | steam | dlrt | slrt | sweep
    versions_per_slot: int = 8      # descriptor slab depth
    reader_lanes: int = 8           # announcement-board lanes
    ring_capacity: int = 0          # retire ring; 0 = sized from the store
    # GC kernel path; None = by platform (see resolve_kernel)
    use_kernel: Optional[bool] = None        # Pallas kernels vs the lax path
    kernel_interpret: Optional[bool] = None  # Pallas interpreter (CPU only)
    slab_watermark: float = 0.75    # vstore capacity_gate slab threshold
    ring_watermark: float = 0.5     # vstore capacity_gate ring threshold
    page_watermark: float = 0.25    # paged-pool free-fraction threshold
    hot_k: int = 8                  # hot-slot count for targeted reclaim
    max_reclaim_rounds: int = 3     # reclaim-and-retry attempts per step
    # multi-host (repro.dist.mvgc): a stalled host's stale announcement is
    # aged out of the global LWM after this budget; inf = defer to the
    # engine's StepWatchdog-derived budget (StepWatchdog.budget_s)
    stale_after_s: float = math.inf

    def kernel_kwargs(self) -> Dict[str, bool]:
        """The (use_kernel, interpret) pair most vstore/paged calls take,
        resolved for the platform by :func:`resolve_kernel`."""
        use_kernel, interpret = resolve_kernel(self.use_kernel,
                                               self.kernel_interpret)
        return {"use_kernel": use_kernel, "interpret": interpret}

    def replace(self, **kw) -> "GCConfig":
        """``dataclasses.replace`` shorthand."""
        return dataclasses.replace(self, **kw)


def resolve_kernel(use_kernel: Optional[bool] = None,
                   interpret: Optional[bool] = None) -> Tuple[bool, bool]:
    """The one place that picks the GC kernels' path: ``(use_kernel,
    interpret)`` with every ``None`` filled in from ``jax.default_backend()``.

    On a TPU an unset choice gives the compiled Pallas kernels; elsewhere it
    gives the lax reference path, and the Pallas interpreter if a caller
    asks for the kernels.  Explicit values are kept, except that a kernel
    interpreted on a TPU raises: a chip run never falls back to it."""
    import jax

    on_tpu = jax.default_backend() == "tpu"
    if use_kernel is None:
        use_kernel = on_tpu
    if interpret is None:
        interpret = not on_tpu
    if on_tpu and use_kernel and interpret:
        raise ValueError(
            "interpret=True on a TPU: the Pallas interpreter is for CPU "
            "validation; leave interpret unset to run the compiled kernels")
    return bool(use_kernel), bool(interpret)


def resolve_gc_config(gc: Optional[GCConfig], where: str,
                      **legacy: Any) -> GCConfig:
    """Fold deprecated per-call GC kwargs into a :class:`GCConfig`.

    ``legacy`` maps GCConfig field names to the values the caller passed for
    the old kwargs (``None`` = not passed).  Any non-``None`` legacy value
    emits one :class:`DeprecationWarning` naming ``where`` and overrides the
    corresponding field — matching the pre-redesign behaviour exactly while
    steering callers to ``gc=GCConfig(...)``.
    """
    base = gc if gc is not None else GCConfig()
    passed = {k: v for k, v in legacy.items() if v is not None}
    if passed:
        warnings.warn(
            f"{where}: keyword(s) {sorted(passed)} are deprecated; pass "
            f"gc=GCConfig(...) instead (DESIGN.md §13)",
            DeprecationWarning, stacklevel=3)
        base = dataclasses.replace(base, **passed)
    return base
