"""The reduction of the program's own spans and dispatches
(`chipbench.program_trace`): on the hand-written trace of
``test_chipbench_trace`` with the program's events added (every number
below is worked out from it), on the small trace recorded on a TPU v5e,
and on a trace of the paged engine's calls taken here on the CPU."""
from __future__ import annotations

import collections
import glob
import os

import pytest
from jax.profiler import ProfileData

from chipbench.trace import Trace
from test_chipbench_trace import DATA, HAND, _events, _meta

# HAND's device plane, and its host spans as written there
_HAND_DEVICE = HAND[:HAND.index('planes { id: 2 name: "/host:CPU"')]
_HAND_SPANS = [(1, 100, 1000), (2, 100, 460), (3, 550, 550), (4, 600, 100)]
_HAND_NAMES = ["chipbench.window", "chipbench.append", "chipbench.reader",
               "chipbench.audit"]
assert HAND == (_HAND_DEVICE + 'planes { id: 2 name: "/host:CPU"\n'
                + _events(1, "python", 0, _HAND_SPANS) + _meta(_HAND_NAMES)
                + "}\n")


@pytest.fixture(scope="module")
def hand():
    return Trace.from_profile(ProfileData.from_text_proto(HAND))


@pytest.fixture(scope="module")
def recorded():
    return Trace.from_file(os.path.join(DATA, "paged_small.xplane.pb"))


# HAND with the program's own events on the host thread (times in ns):
# engine steps [110, 555] and [950, 1060]; syncs [120, 130], [210, 220],
# [520, 552], [1040, 1055], and [660, 670] inside the audit; an append
# span [140, 200]; snapshot pin [710, 760] and reads [770, 850] and, inside
# the audit, [610, 650].  Each dispatch is a nested pair: pool_append
# [141, 190], pool_live [230, 240], gc_gate [960, 970] inside the steps,
# snapshot_read [780, 800] inside the read, kv [102, 108] outside every
# engine span, pool_reset [630, 640] inside the audit.  Host reads of
# device values: one inside each sync, one inside the audit, one at
# [300, 310] inside the first step and outside its syncs.
# Idle gaps, where no program runs (HAND's programs [100, 400], [900,
# 1000], and [650, 680] inside the audit): [400, 600] (midpoint 500: step
# 0, outside its syncs), [700, 900] (800: the read [770, 850]), [1000,
# 1100] (1050: step 1, sync [1040, 1055]).
_PROGRAM = [
    ("repro.engine.step", [(110, 445), (950, 110)]),
    ("repro.engine.sync", [(120, 10), (210, 10), (520, 32), (660, 10),
                           (1040, 15)]),
    ("repro.pool.append", [(140, 60)]),
    ("repro.snapshot.read", [(610, 40), (770, 80)]),
    ("repro.snapshot.pin", [(710, 50)]),
    ("PjitFunction(pool_append)", [(141, 49), (142, 47)]),
    ("PjitFunction(pool_live)", [(230, 10), (231, 8)]),
    ("PjitFunction(snapshot_read)", [(780, 20), (781, 18)]),
    ("PjitFunction(gc_gate)", [(960, 10), (961, 8)]),
    ("PjitFunction(kv)", [(102, 6), (103, 4)]),
    ("PjitFunction(pool_reset)", [(630, 10), (631, 8)]),
    ("np.asarray(jax.Array)", [(121, 8), (211, 8), (521, 28), (1041, 13),
                               (665, 3), (300, 10)]),
]
HAND_PROGRAM = (
    _HAND_DEVICE
    + 'planes { id: 2 name: "/host:CPU"\n'
    + _events(1, "python", 0, _HAND_SPANS + [
        (len(_HAND_NAMES) + k, o, d)
        for k, (_, evs) in enumerate(_PROGRAM, 1) for o, d in evs])
    + _meta(_HAND_NAMES + [n for n, _ in _PROGRAM])
    + "}\n")
NEW_METRICS = ("engine.syncs_per_step", "engine.programs_per_step",
               "engine.idle_ms_per_step", "snapshot.idle_ms_per_read")


def _trace_dir(tmp_path, text):
    """A directory holding ``text`` as a profiler's ``.xplane.pb``."""
    (tmp_path / "t.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(text))
    return str(tmp_path)


def _read_new(trace_dir, obs):
    from chipbench import harness
    from chipbench.run import HERE, load_module
    r = harness.Run(workload="w", config={}, traffic={}, seed=0, seconds=1,
                    trace=True, chips=1, t_start=0.0, trace_dir=trace_dir)
    r.obs = obs
    trace = Trace.from_dir(trace_dir)
    return trace, {m: load_module(HERE / "metrics" / f"{m}.py").read(
        r, trace, {}) for m in NEW_METRICS}


def test_program_spans_leave_every_trace_number_as_it_was(hand):
    both = Trace.from_profile(ProfileData.from_text_proto(HAND_PROGRAM))
    assert both.window_s == hand.window_s
    assert both.busy_s() == hand.busy_s()
    assert both.module_seconds("jit_append_tokens") == \
        hand.module_seconds("jit_append_tokens")
    assert both.exposed_s(lambda e: e.name == "all-reduce.3") == \
        hand.exposed_s(lambda e: e.name == "all-reduce.3")
    assert [e.name for e in both.host] == [e.name for e in hand.host]
    assert both.breakdown() == hand.breakdown()


@pytest.mark.parametrize("obs", [{"steps": 2}, {"decode_ctx": [5, 6]}],
                         ids=["paged", "decode"])
def test_program_metrics_by_hand(tmp_path, obs):
    _, got = _read_new(_trace_dir(tmp_path, HAND_PROGRAM), obs)
    assert got == {
        # four syncs in the window (the fifth is inside the audit), 2 steps
        "engine.syncs_per_step": pytest.approx(2.0),
        # pool_append, pool_live, gc_gate: each pair counted once
        "engine.programs_per_step": pytest.approx(1.5),
        # gaps [400, 600] and [1000, 1100]: 300 ns over 2 steps
        "engine.idle_ms_per_step": pytest.approx(1.5e-4),
        # gap [700, 900] over the one read outside the audit
        "snapshot.idle_ms_per_read": pytest.approx(2e-4),
    }


def test_program_trace_parts_by_hand(tmp_path):
    from chipbench import program_trace
    trace = Trace.from_dir(_trace_dir(tmp_path, HAND_PROGRAM))
    prog = program_trace.ProgramTrace.from_dir(str(tmp_path), trace)
    assert program_trace.load(None, trace) is prog
    assert [e.name for e in prog.dispatches] == [
        "kv", "pool_append", "pool_live", "snapshot_read", "gc_gate"]
    assert [e.name for e in prog.inside(prog.dispatches,
                                        program_trace.ENGINE)] == [
        "pool_append", "pool_live", "gc_gate"]
    assert len(prog.reads) == 5
    assert len(prog.inside(prog.reads, ["repro.engine.sync"])) == 4
    # [400, 500] is busy with operations but runs no program
    assert prog.idle_gaps() == [(400, 600), (700, 900), (1000, 1100)]
    assert prog.idle_by_span() == {
        "repro.engine.step": pytest.approx(200e-9),
        "repro.snapshot.read": pytest.approx(200e-9),
        "repro.engine.sync": pytest.approx(100e-9)}


def test_program_metrics_read_nothing_without_program_spans(tmp_path):
    """A program that writes no spans (as before they existed) gives no
    reading, and the metrics are left out of the result."""
    _, got = _read_new(_trace_dir(tmp_path, HAND), {"steps": 2})
    assert got == dict.fromkeys(NEW_METRICS)


def test_programs_per_step_reads_nothing_without_dispatch_events(tmp_path):
    """A trace with the program's spans but no dispatch events gives no
    program count (not a count of 0); the other three still read."""
    kept = [(n, evs) for n, evs in _PROGRAM
            if not n.startswith("PjitFunction(")]
    text = (_HAND_DEVICE + 'planes { id: 2 name: "/host:CPU"\n'
            + _events(1, "python", 0, _HAND_SPANS + [
                (len(_HAND_NAMES) + k, o, d)
                for k, (_, evs) in enumerate(kept, 1) for o, d in evs])
            + _meta(_HAND_NAMES + [n for n, _ in kept]) + "}\n")
    _, got = _read_new(_trace_dir(tmp_path, text), {"steps": 2})
    assert got["engine.programs_per_step"] is None
    assert got["engine.syncs_per_step"] == pytest.approx(2.0)
    assert got["engine.idle_ms_per_step"] == pytest.approx(1.5e-4)
    assert got["snapshot.idle_ms_per_read"] == pytest.approx(2e-4)


def test_recorded_dispatches_match_program_runs(recorded):
    """Each dispatch writes two nested events; counted once, the window's
    dispatches are the trace's 34 program runs (the last runs after the
    window's end), name by name where the program has a name of its
    own."""
    from chipbench import program_trace
    prog = program_trace.ProgramTrace.from_dir(DATA, recorded)
    runs = recorded.modules[0]
    assert len(runs) == 34 and len(prog.dispatches) == 34
    by_run = collections.Counter(e.name.split("(")[0] for e in runs)
    by_dispatch = collections.Counter("jit_" + e.name
                                      for e in prog.dispatches)
    unknown = by_run.pop("jit__unknown")
    named = {k: v for k, v in by_dispatch.items() if k in by_run}
    assert named == by_run
    assert sum(by_dispatch.values()) - sum(named.values()) == unknown
    # the profiler's own view of the host reads of device values
    assert len(prog.reads) == 19


def test_paged_trace_shows_only_named_programs_in_engine_spans(tmp_path):
    """A real profiler trace of engine calls (on the CPU here), taken with
    the Python call tracer off as the benchmark takes it: the profiler
    still writes the dispatch events, inside the engine's and the
    snapshot's spans every dispatch is one of the engine's named programs
    (no eager one-op program), there is one sync span per counted sync,
    and every host read of a device value lies in a sync span."""
    import jax
    import jax.numpy as jnp

    from chipbench import program_trace
    from repro.core.telemetry import GCConfig
    from repro.serve.engine import PagedKVEngine

    # 4 sequences, a pool of 8 pages of 2 tokens, 2 versions per slab:
    # reclaim passes run within a few steps
    e = PagedKVEngine(4, 8, 2, 4, 1, 4, gc=GCConfig(
        policy="slrt", versions_per_slot=2, reader_lanes=2))
    ids = jnp.arange(4, dtype=jnp.int32)
    kv = jnp.ones((4, 1, 4), jnp.float32)
    on = jnp.ones((4,), bool)

    def calls():
        t = e.pin(0)
        for _ in range(4):
            e.step(ids, kv, kv, on)
        e.view_at(t)
        e.view_at(t, ids[:2])
        e.unpin(0)
        e.reset(ids, on)

    calls()                                  # compile outside the trace
    syncs0, reclaims0 = e.counters.host_syncs, e.stats.reclaims_triggered
    # as the benchmark's window takes it: the Python call tracer off
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    calls()
    jax.profiler.stop_trace()
    assert e.stats.reclaims_triggered > reclaims0

    pdata = ProfileData.from_file(glob.glob(
        str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0])
    host = [ev for p in pdata.planes if p.name.startswith("/host")
            for line in p.lines for ev in line.events]
    lo = min(ev.start_ns for ev in host)
    hi = max(ev.end_ns for ev in host)
    # no device plane on the CPU: a window over the whole trace, idle
    prog = program_trace.ProgramTrace.from_profile(
        pdata, Trace({0: []}, {}, [], window=(lo, hi + 1)))
    both = program_trace.ENGINE + program_trace.SNAPSHOT
    named = {"pool_append", "pool_reset", "pool_live", "gc_gate", "gc_hot",
             "gc_reclaim", "snapshot_read", "snapshot_pin",
             "snapshot_unpin"}
    inside = {d.name for d in prog.inside(prog.dispatches, both)}
    assert {"pool_append", "pool_live", "gc_reclaim",
            "snapshot_read"} <= inside <= named
    assert prog.count("repro.engine.sync") == \
        e.counters.host_syncs - syncs0
    assert prog.count("repro.engine.step") == 4
    in_sync = prog.inside(prog.reads, ["repro.engine.sync"])
    assert len(prog.inside(prog.reads, both)) == len(in_sync) > 0
