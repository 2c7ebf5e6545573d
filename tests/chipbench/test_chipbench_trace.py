"""The trace reduction, on a trace written out by hand (every number below
is worked out from it) and on a small trace recorded on a TPU v5e."""
from __future__ import annotations

import glob
import os

import pytest
from jax.profiler import ProfileData

from chipbench.trace import Trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _events(line_id, name, base_ns, events):
    body = "".join(
        f"events {{ metadata_id: {m} offset_ps: {o * 1000} "
        f"duration_ps: {d * 1000} }}\n" for m, o, d in events)
    return (f'lines {{ id: {line_id} name: "{name}" timestamp_ns: {base_ns}\n'
            f"{body}}}\n")


def _meta(names):
    return "".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                   f'name: "{n}" }} }}\n' for i, n in enumerate(names, 1))


# One device, times in ns from 0.  Window [100, 1100]; audit (clock
# stopped) [600, 700].  Ops: fusion.1 [100, 300], custom-call.2 [250, 400]
# (overlaps), all-reduce.3 [350, 500] (50 of it under custom-call.2),
# fusion.1 [650, 680] (inside the audit), fusion.1 [900, 1000].
# Programs: jit_append_tokens [100, 400] and [900, 1000], jit_x [650, 680].
# Host spans: chipbench.append [100, 560], chipbench.reader [550, 1100],
# chipbench.audit [600, 700].
HAND = (
    'planes { id: 1 name: "/device:TPU:0"\n'
    + _events(1, "XLA Ops", 0, [(1, 100, 200), (2, 250, 150), (3, 350, 150),
                                (1, 650, 30), (1, 900, 100)])
    + _events(2, "XLA Modules", 0, [(4, 100, 300), (4, 900, 100),
                                    (5, 650, 30)])
    + _meta(["fusion.1", "custom-call.2", "all-reduce.3",
             "jit_append_tokens(7)", "jit_x(8)"])
    + "}\n"
    + 'planes { id: 2 name: "/host:CPU"\n'
    + _events(1, "python", 0, [(1, 100, 1000), (2, 100, 460),
                               (3, 550, 550), (4, 600, 100)])
    + _meta(["chipbench.window", "chipbench.append", "chipbench.reader",
             "chipbench.audit"])
    + "}\n")


@pytest.fixture(scope="module")
def hand():
    return Trace.from_profile(ProfileData.from_text_proto(HAND))


def test_window_leaves_out_the_stopped_clock(hand):
    assert hand.window_s == pytest.approx(900e-9)


def test_busy_is_the_union_of_operations(hand):
    # [100, 500] and [900, 1000]; the op inside the audit is left out
    assert hand.busy_s() == pytest.approx(500e-9)


def test_program_time_by_name(hand):
    assert hand.module_seconds("jit_append_tokens") == (
        pytest.approx(400e-9), 2)
    assert hand.module_seconds("jit_x") == (0, 0)


def test_operations_by_name(hand):
    evs = hand.op_events(lambda e: e.name == "fusion.1")
    assert sum(e.dur for e in evs) == pytest.approx(300)


def test_collective_time_with_no_compute_beside_it(hand):
    from chipbench.metrics_util import is_collective
    # all-reduce.3 [350, 500], 50 of it under custom-call.2
    assert hand.exposed_s(lambda e: e.name == "all-reduce.3") == \
        pytest.approx(100e-9)
    assert is_collective(type("E", (), {
        "name": "%collective-permute-start.2 = (s32[1]) "
                "collective-permute-start(s32[1] %x)"})())
    assert not is_collective(type("E", (), {"name": "%fusion.1 = s32[1]"})())


def test_breakdown(hand):
    b = hand.breakdown()
    assert b["device_ops"][0] == ["fusion.1", pytest.approx(300e-9)]
    # idle gaps [500, 600] (midpoint 550: append, the innermost span),
    # [700, 900] and [1000, 1100] (reader); the audit is no gap
    assert dict((k, v) for k, v in b["idle_gaps"]) == {
        "chipbench.append": pytest.approx(100e-9),
        "chipbench.reader": pytest.approx(300e-9)}


def test_a_trace_without_tpu_operations_is_refused():
    cpu_only = ('planes { id: 2 name: "/host:CPU"\n'
                + _events(1, "python", 0, [(1, 0, 10)])
                + _meta(["chipbench.window"]) + "}\n")
    with pytest.raises(ValueError):
        Trace.from_profile(ProfileData.from_text_proto(cpu_only))


# A trace recorded on one TPU v5e: two steps of the 12288-page paged
# cache, a reader's 8-sequence snapshot read, and one reclaim pass, in the
# benchmark's spans.
@pytest.fixture(scope="module")
def recorded():
    return Trace.from_file(os.path.join(DATA, "paged_small.xplane.pb"))


def _naive_busy_ns(trace):
    """Busy time by brute force: walk the operations in start order."""
    evs = sorted((e.start, e.end) for e in trace.ops[0]
                 if trace.w0 <= e.start < trace.w1)
    busy, cur_s, cur_e = 0, None, None
    for s, e in evs:
        s, e = max(s, trace.w0), min(e, trace.w1)
        if cur_e is None or s > cur_e:
            busy += 0 if cur_e is None else cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    return busy + (cur_e - cur_s)


def test_recorded_busy_and_window(recorded):
    assert recorded.devices == [0]
    assert 0 < recorded.busy_s() < recorded.window_s
    assert recorded.busy_s() == pytest.approx(_naive_busy_ns(recorded) / 1e9)


def test_recorded_kernels_and_programs(recorded):
    from chipbench import counts
    from chipbench.metrics_util import first_shape, kernel_runs, reclaim_runs
    compact = kernel_runs(recorded, "compact")
    # the reclaim pass: the ring flush's implicated slots (2048 rows) and
    # the 8 hot slots, each over 8 versions
    assert sorted(first_shape(e) for e in compact) == [(8, 8), (2048, 8)]
    gather = kernel_runs(recorded, "search_gather")
    assert [first_shape(e) for e in gather] == [(8, 193)]
    assert len(reclaim_runs(recorded)) == 1
    assert counts.compact_bytes(2048, 8) == 2048 * 8 * 4 * 7 + 2048 * 4


def test_recorded_metric_readers(recorded):
    from chipbench import harness
    from chipbench.run import HERE, load_module
    r = harness.Run(workload="w", config={}, traffic={}, seed=0, seconds=1,
                    trace=True, chips=1, t_start=0.0)
    r.obs = {"steps": 2, "shapes": {"versions": 8, "max_pages": 192}}
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}
    got = {m: load_module(HERE / "metrics" / f"{m}.py").read(r, recorded,
                                                             peaks)
           for m in ("compact_roofline", "search_gather_roofline",
                     "reclaim.ms_per_step", "append.ms_per_step",
                     "device.idle_share")}
    for name, value in got.items():
        assert value is not None and value > 0, name
    assert got["compact_roofline"] < 100
    assert got["search_gather_roofline"] < 100
    assert got["device.idle_share"] == pytest.approx(
        100 * (1 - recorded.busy_s() / recorded.window_s))
    b = recorded.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
