"""Sharded multi-host MVGC: global-LWM safety and straggler tolerance
(repro.dist.mvgc, DESIGN.md §13).

Everything here runs on one CPU device — the protocol is placement-
independent (``global_lwm`` degrades to a plain ``min`` when the stack is
unsharded), so these tests exercise the exact shard/LWM/aging logic the
fake-device subprocess tests in ``test_dist_unit.py`` run over a real
``reduce="min"`` ring."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

jax.config.update("jax_platform_name", "cpu")

from repro.core.mvgc.pool import EMPTY, TS_MAX
from repro.core.telemetry import GCConfig, PressureSignal
from repro.dist.mvgc import (ShardedPagedKVEngine, age_out_stale, global_lwm,
                             lwm_contributions, stack_states)
from repro.mvkv import paged

B, NP, PS, MP, KVH, HD = 4, 12, 4, 3, 1, 4
GC = GCConfig(policy="slrt", versions_per_slot=6, reader_lanes=4)


def _engine(hosts: int, gc: GCConfig = GC) -> ShardedPagedKVEngine:
    return ShardedPagedKVEngine(hosts, B, NP, PS, MP, KVH, HD, gc=gc)


def _kv(hosts: int, step: int) -> jnp.ndarray:
    """Per-(host, step, seq) distinct payloads: a wrongly reclaimed page
    shows up as a value mismatch, not just a shape change."""
    base = (np.arange(hosts * B, dtype=np.float32).reshape(hosts, B)
            + hosts * B * (step + 1))
    return jnp.asarray(np.broadcast_to(
        base[:, :, None, None], (hosts, B, KVH, HD)))


def _seq_ids(hosts: int) -> jnp.ndarray:
    return jnp.broadcast_to(jnp.arange(B, dtype=jnp.int32), (hosts, B))


def _checksum(local_st, tables: np.ndarray, lengths: np.ndarray) -> tuple:
    k = np.asarray(local_st.k_pages)[:, :, 0, 0]
    out = []
    for s in range(tables.shape[0]):
        n = int(lengths[s])
        out.append((n, tuple(
            float(k[int(tables[s, j // PS]), j % PS]) for j in range(n))))
    return tuple(out)


def _churn(eng: ShardedPagedKVEngine, steps: int, start: int = 0) -> None:
    """Append/reset churn that retires versions and recycles pages on every
    host — the workload under which reclamation must stay pin-safe."""
    hosts = eng.hosts
    seq = _seq_ids(hosts)
    all_on = jnp.ones((hosts, B), bool)
    for step in range(start, start + steps):
        eng.step(seq, _kv(hosts, step), _kv(hosts, step), all_on)
        if step % 3 == 2:
            done = np.zeros((hosts, B), bool)
            done[:, step % B] = True
            eng.reset(seq, jnp.asarray(done))


# ---------------------------------------------------------------------------
# building blocks (single device, fast)
# ---------------------------------------------------------------------------
class TestBuildingBlocks:
    def test_stack_states_adds_host_dim(self):
        base = paged.make_paged_kv(B, NP, PS, MP, KVH, HD, gc=GC)
        st = stack_states(base, 3)
        for leaf, orig in zip(jax.tree.leaves(st), jax.tree.leaves(base)):
            assert leaf.shape == (3,) + orig.shape
            np.testing.assert_array_equal(np.asarray(leaf[1]),
                                          np.asarray(orig))

    def test_lwm_contributions_sentinel_and_pins(self):
        eng = _engine(3)
        contrib = np.asarray(lwm_contributions(eng.st))
        assert (contrib == int(TS_MAX)).all()       # pin-free boards
        ts = eng.pin(1, 0)
        contrib = np.asarray(lwm_contributions(eng.st))
        assert contrib[1] == ts
        assert contrib[0] == contrib[2] == int(TS_MAX)

    def test_age_out_stale_replaces_and_counts(self):
        contrib = jnp.asarray([15, 7, int(TS_MAX)], jnp.int32)
        aged, n = age_out_stale(contrib, [0.0, 100.0, 100.0], 5.0)
        np.testing.assert_array_equal(
            np.asarray(aged), [15, int(TS_MAX), int(TS_MAX)])
        # only the stale *pinning* lane counts (TS_MAX was already inert)
        assert int(n) == 1

    def test_global_lwm_without_ring(self):
        contrib = jnp.asarray([23, 5, int(TS_MAX)], jnp.int32)
        assert int(global_lwm(contrib)) == 5
        assert int(global_lwm(jnp.full((4,), TS_MAX, jnp.int32))) \
            == int(TS_MAX)

    def test_pressure_is_unified_signal_with_host_dim(self):
        eng = _engine(2)
        sig = eng.pressure()
        assert isinstance(sig, PressureSignal)
        assert sig.under_pressure.shape == (2,)
        assert sig.capacity.shape == (2,)
        np.testing.assert_array_equal(np.asarray(sig.capacity), [NP, NP])


# ---------------------------------------------------------------------------
# differential: sharded shards replay the single-host vstore bit-for-bit
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("policy", ["ebr", "slrt"])
def test_sharded_trace_matches_single_host(policy):
    """The same op trace through (a) the single-host paged stack and (b) the
    host-stacked vmapped stack with the inert TS_MAX global pin must land in
    bit-identical states on every host — sharding changes placement, never
    the protocol."""
    gc = GCConfig(policy=policy, versions_per_slot=6, reader_lanes=4)
    hosts = 3
    single = paged.make_paged_kv(B, NP, PS, MP, KVH, HD, gc=gc)
    stacked = stack_states(single, hosts)
    sentinel = jnp.full((hosts, 1), TS_MAX, jnp.int32)

    app1 = jax.jit(functools.partial(paged.append_tokens, gc_policy=policy))
    rst1 = jax.jit(functools.partial(paged.reset_sequence, gc_policy=policy))
    rec1 = jax.jit(functools.partial(paged.reclaim_on_pressure,
                                     gc_policy=policy))
    apph = jax.jit(jax.vmap(lambda s, q, k, v, m, p: paged.append_tokens(
        s, q, k, v, m, gc_policy=policy, extra_pins=p)))
    rsth = jax.jit(jax.vmap(lambda s, q, m, p: paged.reset_sequence(
        s, q, m, gc_policy=policy, extra_pins=p)))
    rech = jax.jit(jax.vmap(lambda s, h, d, p: paged.reclaim_on_pressure(
        s, h, d, gc_policy=policy, extra_pins=p)))

    seq1 = jnp.arange(B, dtype=jnp.int32)
    seqh = _seq_ids(hosts)
    on1 = jnp.ones((B,), bool)
    onh = jnp.ones((hosts, B), bool)
    for step in range(12):
        kv1 = _kv(1, step)[0]
        kvh = jnp.broadcast_to(kv1[None], (hosts, B, KVH, HD))
        single, f1 = app1(single, seq1, kv1, kv1, on1)
        stacked, fh = apph(stacked, seqh, kvh, kvh, onh, sentinel)
        np.testing.assert_array_equal(np.asarray(fh[1]), np.asarray(f1))
        if step % 4 == 3:
            done1 = on1 & (seq1 == step % B)
            single, _ = rst1(single, seq1, done1)
            stacked, _ = rsth(stacked, seqh,
                              jnp.broadcast_to(done1[None], (hosts, B)),
                              sentinel)
        if step % 5 == 4:
            hot1 = paged.hot_sequences(single, k=2)
            single, _ = rec1(single, hot1, jnp.int32(4))
            hoth = jax.vmap(functools.partial(paged.hot_sequences,
                                              k=2))(stacked)
            stacked, _ = rech(stacked, hoth,
                              jnp.full((hosts,), 4, jnp.int32), sentinel)

    for leaf_h, leaf_1 in zip(jax.tree.leaves(stacked),
                              jax.tree.leaves(single)):
        for h in range(hosts):
            np.testing.assert_array_equal(np.asarray(leaf_h[h]),
                                          np.asarray(leaf_1))


@pytest.mark.parametrize("policy", ["ebr", "slrt"])
def test_sharded_engine_matches_single_host_engine(policy):
    """The sharded engine runs the single-host engine's loop on every shard.
    Driven with the same traffic on every host and no pins (the global LWM
    is then the inert TS_MAX sentinel) through the trace of the fused-loop
    test, every host ends each op in the single engine's state with its
    failed lanes; the loop takes the same decisions (pressure events and
    reclaim passes), and the page counts are the single engine's summed
    over the hosts."""
    from paged_trace import B as N, D, HKV, MP, PAGES, PS, trace
    from repro.serve.engine import PagedKVEngine

    hosts = 3
    gc = GCConfig(policy=policy, versions_per_slot=2, reader_lanes=2)
    one = PagedKVEngine(N, PAGES, PS, MP, HKV, D, gc=gc)
    many = ShardedPagedKVEngine(hosts, N, PAGES, PS, MP, HKV, D, gc=gc)
    ids = jnp.arange(N, dtype=jnp.int32)

    def tiled(x):
        x = jnp.asarray(x)
        return jnp.broadcast_to(x[None], (hosts,) + x.shape)

    rng = np.random.default_rng(["ebr", "slrt"].index(policy))
    for n, (op, args) in enumerate(trace(rng, 80)):
        what = f"{policy} op {n}: {op}{args}"
        if op == "step":
            kv = (jnp.full((N, HKV, D), args[1], jnp.float32)
                  + ids[:, None, None])
            mask = jnp.asarray(args[0])
            want = one.step(ids, kv, kv, mask)
            got = many.step(tiled(ids), tiled(kv), tiled(kv), tiled(mask))
        elif op == "reset":
            mask = jnp.asarray(args[0])
            want = one.reset(ids, mask)
            got = many.reset(tiled(ids), tiled(mask))
        elif op == "fork":
            src, dst = (jnp.asarray([x], jnp.int32) for x in args)
            on = jnp.ones((1,), bool)
            want = one.fork(src, dst, on)
            got = many.fork(tiled(src), tiled(dst), tiled(on))
        elif op == "reclaim":
            want = one.reclaim(args[0])
            got = many.reclaim(args[0])
            assert got == hosts * want, what
            got = want = np.zeros((), bool)
        elif op == "arm":
            # arms the checkpoint-eviction post-pass as `checkpoint` does,
            # without writing one
            one.ckpt_max = many.ckpt_max = int(one.st.mv.now)
            got = want = np.zeros((), bool)
        else:
            continue                     # no pins
        for h in range(hosts):
            np.testing.assert_array_equal(got[h] if got.ndim else got,
                                          want, err_msg=what)
        for leaf_h, leaf_1 in zip(jax.tree.leaves(many.st),
                                  jax.tree.leaves(one.st)):
            for h in range(hosts):
                np.testing.assert_array_equal(np.asarray(leaf_h[h]),
                                              np.asarray(leaf_1),
                                              err_msg=what)
        assert (many.pressure_events, many.reclaims_triggered) == \
            (one.pressure_events, one.reclaims_triggered), what
        assert (many.pages_reclaimed, many.peak_pages, many.give_ups) == \
            tuple(hosts * x for x in (one.pages_reclaimed, one.peak_pages,
                                      one.give_ups)), what
    # the trace reached reclaims, give-ups, forks and evictions
    assert one.reclaims_triggered and one.give_ups and one.forks
    assert many.forks == hosts * one.forks
    assert many.stats.ckpt_evictions == hosts * one.stats.ckpt_evictions > 0


# ---------------------------------------------------------------------------
# global-LWM safety: a pin on one host protects snapshots on every host
# ---------------------------------------------------------------------------
def test_pin_on_one_host_protects_every_shard():
    """A reader pins on host 0's board and snapshot-reads *every* host's
    shard at that timestamp (announcement lanes are host-local; only the
    global LWM carries the pin across).  Under churn + forced reclaims, all
    those views must stay byte-identical.  The control run with the LWM
    neutered must corrupt a remote view — proving the global LWM is the
    load-bearing protection, not local boards or luck."""
    def run(neuter_lwm: bool) -> int:
        eng = _engine(4)
        if neuter_lwm:
            sentinel = jnp.full((eng.hosts, 1), TS_MAX, jnp.int32)
            eng.lwm_pins = lambda: sentinel
        _churn(eng, 4)
        ts = eng.pin(0, 0)
        refs = {}
        for h in range(eng.hosts):
            tbl, ln = eng.view_at(h, ts)
            refs[h] = _checksum(eng.host_state(h), np.asarray(tbl),
                                np.asarray(ln))
        _churn(eng, 8, start=4)
        eng.reclaim(deficit=NP)          # full cold-spill sweep, every shard
        _churn(eng, 4, start=12)
        bad = 0
        for h in range(eng.hosts):
            tbl, ln = eng.view_at(h, ts)
            now = _checksum(eng.host_state(h), np.asarray(tbl),
                            np.asarray(ln))
            if now != refs[h]:
                bad += 1
        return bad

    assert run(neuter_lwm=False) == 0
    assert run(neuter_lwm=True) > 0


def test_lwm_tracks_min_over_hosts():
    eng = _engine(3)
    _churn(eng, 3)
    t0 = eng.pin(0, 0)
    _churn(eng, 2, start=3)
    t1 = eng.pin(1, 0)
    assert t1 > t0
    pins = np.asarray(eng.lwm_pins())
    assert pins.shape == (3, 1)
    assert (pins == t0).all()            # min over hosts, broadcast to all
    eng.unpin(0, 0)
    assert (np.asarray(eng.lwm_pins()) == t1).all()
    assert eng.lwm_advances >= 1         # the LWM moved up off a real pin


# ---------------------------------------------------------------------------
# straggler tolerance: a stalled host bounds reclamation, never blocks it
# ---------------------------------------------------------------------------
def test_stalled_host_is_aged_out_and_reclamation_proceeds():
    gc = GCConfig(policy="slrt", versions_per_slot=6, reader_lanes=4,
                  stale_after_s=5.0)
    eng = _engine(4, gc=gc)
    _churn(eng, 4)
    ts = eng.pin(1, 0)                   # the soon-to-stall host pins
    assert (np.asarray(eng.lwm_pins()) == ts).all()

    # host 1 stalls past its staleness budget; its announcement ages out
    ages = np.zeros((4,), np.float32)
    ages[1] = 100.0
    eng.virtual_ages_s = ages
    pins = np.asarray(eng.lwm_pins())
    assert (pins == int(TS_MAX)).all()   # stale pin no longer bounds the LWM
    assert eng.stats.stale_lanes_aged >= 1

    # the remaining hosts keep reclaiming as if the pin were gone
    before = eng.stats.reclaimed
    _churn(eng, 6, start=4)
    eng.reclaim(deficit=NP)
    assert eng.stats.reclaimed > before

    # the stalled host's *local* board still protects its own shard: its
    # held snapshot stays byte-stable even though the mesh moved on
    tbl, ln = eng.view_at(1, ts)
    ref = _checksum(eng.host_state(1), np.asarray(tbl), np.asarray(ln))
    _churn(eng, 3, start=10)
    tbl, ln = eng.view_at(1, ts)
    assert _checksum(eng.host_state(1), np.asarray(tbl),
                     np.asarray(ln)) == ref

    row = eng.space()
    assert row["stale_lanes_aged"] >= 1
    assert row["pages_reclaimed"] > 0


def test_fresh_hosts_never_aged_with_infinite_budget():
    eng = _engine(2)                     # stale_after_s=inf -> watchdog
    _churn(eng, 3)
    assert eng.stats.stale_lanes_aged == 0
    assert (eng.budget_s() > 0).all()    # warmup budget is finite, not inf


def test_mesh_that_cannot_take_the_stack_raises():
    """On several devices the stack is sharded or refused, never silently
    left whole: 4 hosts over 3 devices raise in both the mesh builder and
    the engine, and 4 hosts over 4 devices put one shard on each."""
    import os
    import subprocess
    import sys
    import textwrap
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    code = textwrap.dedent("""
        import jax, numpy as np, pytest
        from repro.core.telemetry import GCConfig
        from repro.dist.mvgc import ShardedPagedKVEngine
        from repro.launch.mesh import make_gc_mesh
        assert len(jax.devices()) == 3
        with pytest.raises(ValueError, match="cannot be laid out"):
            make_gc_mesh(4)
        three = jax.make_mesh((3,), ("gc_hosts",))
        with pytest.raises(ValueError, match="do not divide"):
            ShardedPagedKVEngine(4, 4, 12, 4, 3, 1, 4, mesh=three,
                                 gc=GCConfig(reader_lanes=4))
        eng = ShardedPagedKVEngine(6, 4, 12, 4, 3, 1, 4,
                                   gc=GCConfig(reader_lanes=4))
        assert eng._ring is not None
        for leaf in jax.tree.leaves(eng.st):
            assert len(leaf.sharding.device_set) == 3
            assert leaf.addressable_shards[0].data.shape[0] == 2
        # a host's slice lives on the one device holding its shard, so a
        # kernel reading it runs there and needs no partitioning
        for leaf, full in zip(jax.tree.leaves(eng.host_state(3)),
                              jax.tree.leaves(eng.st)):
            assert leaf.sharding.device_set == {jax.devices()[1]}
            np.testing.assert_array_equal(np.asarray(leaf),
                                          np.asarray(full)[3])
        print("mesh checks OK")
    """)
    env = dict(os.environ, PYTHONPATH=os.path.join(repo, "src"),
               JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=3")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=300, env=env)
    assert out.returncode == 0, f"{out.stdout}\n{out.stderr}"
    assert "mesh checks OK" in out.stdout
