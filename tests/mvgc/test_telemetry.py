"""The unified pressure/telemetry vocabulary (repro.core.telemetry,
DESIGN.md §13): one PressureSignal / ReclaimStats / GCConfig across the
contention manager, the version store, the paged-KV engines and the bench
rows — plus the deprecation shims that keep the old kwarg surface alive for
one release."""
import dataclasses
import math
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

jax.config.update("jax_platform_name", "cpu")

from repro.configs import reduced_config
from repro.configs.base import RunConfig, SHAPES
from repro.core.mvgc import vstore
from repro.core.sim.contention import ContentionManager
from repro.core.telemetry import (GCConfig, PressureSignal, ReclaimStats,
                                  resolve_gc_config)
from repro.mvkv import paged
from repro.serve.engine import PagedKVEngine


# ---------------------------------------------------------------------------
# the vocabulary types
# ---------------------------------------------------------------------------
class TestPressureSignal:
    def test_derived_properties(self):
        sig = PressureSignal(level=0.75, under_pressure=True, deficit=3,
                             live=9, capacity=12)
        assert sig.free_frac == pytest.approx(0.25)
        assert sig.free_pages == 3

    def test_deprecated_aliases_are_the_same_type(self):
        assert vstore.PressureReport is PressureSignal
        assert paged.PagePressure is PressureSignal


class TestReclaimStats:
    def test_accounting_and_row(self):
        st = ReclaimStats(unit="pages")
        st.note_live(10)
        st.note_event()
        st.note_reclaim(4, 6)
        st.note_live(8)
        st.give_ups += 2
        st.stale_lanes_aged += 1
        row = st.as_row()
        assert row["pressure_events"] == 1
        assert row["reclaims_triggered"] == 1
        assert row["pages_reclaimed"] == 4
        assert row["peak_pages"] == 10
        assert row["peak_pages_post_reclaim"] == 6
        assert row["give_ups"] == 2
        assert row["stale_lanes_aged"] == 1

    def test_unit_keys_follow_unit(self):
        row = ReclaimStats(unit="versions").as_row()
        assert "versions_reclaimed" in row and "peak_versions" in row


    def test_ckpt_eviction_fields(self):
        st = ReclaimStats(unit="pages")
        st.note_ckpt_eviction(3, 5)
        st.note_ckpt_eviction(2, 5)
        row = st.as_row()
        assert row["ckpt_evictions"] == 5
        assert row["ckpt_pages_freed"] == 10
        assert "ckpt_versions_freed" in ReclaimStats(unit="versions").as_row()


class TestGCConfig:
    def test_kernel_kwargs(self):
        gc = GCConfig(use_kernel=True, kernel_interpret=False)
        assert gc.kernel_kwargs() == {"use_kernel": True, "interpret": False}

    def test_kernel_path_by_platform(self, monkeypatch):
        """Unset, the platform picks the path: the lax path off a TPU, the
        compiled kernels on one; interpreting on a TPU raises."""
        import jax
        assert GCConfig().kernel_kwargs() == {"use_kernel": False,
                                              "interpret": True}
        assert GCConfig(use_kernel=True).kernel_kwargs() == {
            "use_kernel": True, "interpret": True}
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert GCConfig().kernel_kwargs() == {"use_kernel": True,
                                              "interpret": False}
        assert GCConfig(use_kernel=False).kernel_kwargs() == {
            "use_kernel": False, "interpret": False}
        for gc in (GCConfig(kernel_interpret=True),
                   GCConfig(use_kernel=True, kernel_interpret=True)):
            with pytest.raises(ValueError, match="interpret"):
                gc.kernel_kwargs()

    def test_replace(self):
        gc = GCConfig().replace(policy="ebr", hot_k=2)
        assert gc.policy == "ebr" and gc.hot_k == 2
        assert math.isinf(gc.stale_after_s)       # untouched defaults


# ---------------------------------------------------------------------------
# deprecation shims
# ---------------------------------------------------------------------------
class TestResolveGCConfig:
    def test_gc_passes_through_silently(self):
        gc = GCConfig(policy="ebr")
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            assert resolve_gc_config(gc, "here") is gc
            assert resolve_gc_config(None, "here") == GCConfig()

    def test_legacy_kwarg_warns_and_overrides(self):
        with pytest.warns(DeprecationWarning, match="versions_per_slot"):
            gc = resolve_gc_config(None, "here", versions_per_slot=4)
        assert gc.versions_per_slot == 4
        with pytest.warns(DeprecationWarning, match="here"):
            gc = resolve_gc_config(GCConfig(policy="ebr"), "here", hot_k=2)
        assert gc.policy == "ebr" and gc.hot_k == 2

    def test_make_paged_kv_legacy_matches_gc_config(self):
        with pytest.warns(DeprecationWarning):
            legacy = paged.make_paged_kv(2, 8, 4, 2, 1, 4,
                                         versions_per_seq=4, reader_lanes=2)
        new = paged.make_paged_kv(
            2, 8, 4, 2, 1, 4,
            gc=GCConfig(versions_per_slot=4, reader_lanes=2))
        for a, b in zip(jax.tree.leaves(legacy), jax.tree.leaves(new)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_engine_legacy_kwargs_warn_but_work(self):
        with pytest.warns(DeprecationWarning, match="PagedKVEngine"):
            eng = PagedKVEngine(2, 8, 4, 2, 1, 4, gc_policy="ebr",
                                versions_per_seq=4)
        assert eng.gc.policy == "ebr"
        assert eng.gc.versions_per_slot == 4

    def test_engine_gc_config_is_silent(self):
        with warnings.catch_warnings():
            warnings.simplefilter("error", DeprecationWarning)
            eng = PagedKVEngine(2, 8, 4, 2, 1, 4,
                                gc=GCConfig(policy="ebr"))
        assert eng.gc.policy == "ebr"
        assert isinstance(eng.stats, ReclaimStats)


# ---------------------------------------------------------------------------
# RunConfig <-> GCConfig round trip
# ---------------------------------------------------------------------------
class TestRunConfigGC:
    def test_flat_fields_build_gc(self):
        run = RunConfig(model=reduced_config("minitron-4b"),
                        shape=SHAPES["train_4k"], gc_policy="ebr",
                        versions_per_slot=4, use_kernel=True)
        assert run.gc is not None
        assert run.gc.policy == "ebr"
        assert run.gc.versions_per_slot == 4
        assert run.gc.use_kernel is True

    def test_gc_backfills_flat_fields(self):
        gc = GCConfig(policy="steam", reader_lanes=3, ring_capacity=32)
        run = RunConfig(model=reduced_config("minitron-4b"),
                        shape=SHAPES["train_4k"], gc=gc)
        assert run.gc_policy == "steam"
        assert run.reader_lanes == 3
        assert run.ring_capacity == 32


# ---------------------------------------------------------------------------
# producers speak the vocabulary
# ---------------------------------------------------------------------------
class TestProducers:
    def test_capacity_gate_returns_signal(self):
        st = vstore.make_state(4, 4, 2)
        sig = vstore.capacity_gate(st)
        assert isinstance(sig, PressureSignal)
        assert int(sig.capacity) == 16
        assert int(sig.live) >= 0
        assert float(sig.free_frac) == pytest.approx(1.0 - float(sig.level))

    def test_page_pressure_returns_signal(self):
        st = paged.make_paged_kv(2, 8, 4, 2, 1, 4)
        sig = paged.page_pressure(st)
        assert isinstance(sig, PressureSignal)
        assert int(sig.capacity) == 8
        assert int(sig.live) + int(sig.free_pages) == 8

    def test_contention_manager_signal_and_alias(self):
        cm = ContentionManager(2, capacity=8, pressure_window=16)
        sig = cm.pressure_signal(now=0.0)
        assert isinstance(sig, PressureSignal)
        assert sig.level == 0.0                  # no conflict ever seen
        assert cm.pressure(0.0) == sig.level     # deprecated alias agrees
        cm.record_conflict(0, "wcc", now=10.0)
        assert cm.pressure_signal(10.0).level == pytest.approx(1.0)
        assert cm.pressure_signal(18.0).level == pytest.approx(0.5)

    def test_engine_stats_properties_delegate(self):
        eng = PagedKVEngine(2, 8, 4, 2, 1, 4, gc=GCConfig())
        eng.stats.note_event()
        eng.stats.note_reclaim(3, 2)
        assert eng.pressure_events == 1
        assert eng.reclaims_triggered == 1
        assert eng.pages_reclaimed == 3
