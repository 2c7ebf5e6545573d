"""Per-kernel interpret-mode validation: shape/dtype sweeps vs ref.py oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

jax.config.update("jax_platform_name", "cpu")

from repro.core.mvgc.needed import needed_intervals
from repro.kernels.compact.ops import compact as compact_fused
from repro.kernels.compact.ops import needed as compact_needed
from repro.kernels.compact.ref import compact_ref, needed_ref
from repro.kernels.decode_attention.ops import paged_decode
from repro.kernels.decode_attention.ref import paged_decode_ref
from repro.kernels.flash_prefill.ops import flash_attention
from repro.kernels.flash_prefill.ref import attention_ref
from repro.kernels.version_search.ops import search, search_gather
from repro.kernels.version_search.ref import search_gather_ref, search_ref

TS_MAX = np.iinfo(np.int32).max


def _mk_slabs(rng, S, V, max_ts=200):
    """Random valid version slabs: per slot, k versions with increasing ts,
    chained succ, newest current."""
    ts = np.full((S, V), -1, np.int32)
    succ = np.full((S, V), TS_MAX, np.int32)
    pay = np.full((S, V), -1, np.int32)
    for s in range(S):
        k = rng.integers(0, V + 1)
        times = np.sort(rng.choice(np.arange(1, max_ts), size=k, replace=False))
        perm = rng.permutation(V)[:k]  # versions scattered across the slab row
        for i, (slot_v, t) in enumerate(zip(perm, times)):
            ts[s, slot_v] = t
            succ[s, slot_v] = times[i + 1] if i + 1 < k else TS_MAX
            pay[s, slot_v] = 1000 * s + i
    return jnp.array(ts), jnp.array(succ), jnp.array(pay)


class TestCompactKernel:
    @pytest.mark.parametrize("S,V,P", [(8, 4, 4), (64, 8, 16), (200, 16, 8),
                                       (256, 8, 128), (33, 5, 3)])
    def test_matches_ref(self, S, V, P):
        rng = np.random.default_rng(S * 31 + V)
        ts, succ, _ = _mk_slabs(rng, S, V)
        ann = np.sort(rng.choice(np.arange(0, 220), size=P, replace=False)).astype(np.int32)
        # pad half the lanes to TS_MAX (idle readers)
        ann[P // 2 :] = TS_MAX
        ann = jnp.array(np.sort(ann))
        now = jnp.int32(150)
        got = compact_needed(ts, succ, ann, now, use_kernel=True, interpret=True)
        want = needed_ref(ts, succ, ann, now)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        # the searchsorted formulation in core/mvgc agrees too
        want2 = needed_intervals(ts, succ, ann, now)
        np.testing.assert_array_equal(np.asarray(want), np.asarray(want2))

    def test_block_boundary(self):
        rng = np.random.default_rng(0)
        ts, succ, _ = _mk_slabs(rng, 70, 4)  # S not divisible by block
        ann = jnp.array([5, 50, TS_MAX, TS_MAX], jnp.int32)
        got = compact_needed(ts, succ, ann, jnp.int32(60), block_s=32,
                             use_kernel=True, interpret=True)
        want = needed_ref(ts, succ, ann, jnp.int32(60))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _assert_compact_matches(ts, succ, pay, mask, ann, now, **kw):
    got = compact_fused(ts, succ, pay, mask, ann, now,
                        use_kernel=True, interpret=True, **kw)
    want = compact_ref(ts, succ, pay, mask, ann, now)
    for g, w, name in zip(got, want, ("ts", "succ", "payload", "freed", "n")):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w), err_msg=name)
    return got


class TestCompactFusedKernel:
    """Fused needed+splice (DESIGN.md §12) vs the compact_ref oracle."""

    @pytest.mark.parametrize("S,V,P", [(8, 4, 4), (64, 8, 16), (200, 16, 8),
                                       (33, 5, 3)])
    def test_matches_ref(self, S, V, P):
        rng = np.random.default_rng(S * 17 + V)
        ts, succ, pay = _mk_slabs(rng, S, V)
        ann = np.sort(rng.choice(np.arange(0, 220), size=P, replace=False)).astype(np.int32)
        ann[P // 2 :] = TS_MAX
        ann = jnp.array(np.sort(ann))
        mask = jnp.array(rng.random(S) < 0.8)
        _assert_compact_matches(ts, succ, pay, mask, ann, jnp.int32(150))

    def test_block_boundary(self):
        rng = np.random.default_rng(5)
        ts, succ, pay = _mk_slabs(rng, 70, 4)  # R not divisible by block_r
        ann = jnp.array([5, 50, TS_MAX, TS_MAX], jnp.int32)
        mask = jnp.ones((70,), bool)
        _assert_compact_matches(ts, succ, pay, mask, ann, jnp.int32(200),
                                block_r=32)

    def test_empty_chains(self):
        """All-EMPTY slabs: nothing spliced, nothing freed."""
        S, V = 16, 4
        ts = jnp.full((S, V), -1, jnp.int32)
        succ = jnp.full((S, V), TS_MAX, jnp.int32)
        pay = jnp.full((S, V), -1, jnp.int32)
        ann = jnp.full((4,), TS_MAX, jnp.int32)
        got = _assert_compact_matches(ts, succ, pay, jnp.ones((S,), bool),
                                      ann, jnp.int32(10))
        assert int(got[4]) == 0

    def test_all_needed(self):
        """now == 0: every version is still open (succ > now), so the fused
        pass must splice nothing even with idle readers."""
        rng = np.random.default_rng(9)
        ts, succ, pay = _mk_slabs(rng, 24, 6)
        ann = jnp.full((4,), TS_MAX, jnp.int32)
        got = _assert_compact_matches(ts, succ, pay, jnp.ones((24,), bool),
                                      ann, jnp.int32(0))
        assert int(got[4]) == 0
        np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(ts))

    def test_single_version_slots(self):
        """One current version per slot (succ == TS_MAX): always needed."""
        S, V = 12, 4
        ts = np.full((S, V), -1, np.int32)
        pay = np.full((S, V), -1, np.int32)
        succ = np.full((S, V), TS_MAX, np.int32)
        for s in range(S):
            ts[s, s % V] = s + 1
            pay[s, s % V] = 100 + s
        ann = jnp.full((4,), TS_MAX, jnp.int32)
        got = _assert_compact_matches(jnp.array(ts), jnp.array(succ),
                                      jnp.array(pay), jnp.ones((S,), bool),
                                      ann, jnp.int32(500))
        assert int(got[4]) == 0

    def test_pinned_lane_masks(self):
        """A pin inside a closed interval keeps exactly that version; rows
        with mask False pass through untouched even when fully dead."""
        rng = np.random.default_rng(21)
        ts, succ, pay = _mk_slabs(rng, 40, 6)
        ann = jnp.array([40, 90, TS_MAX, TS_MAX], jnp.int32)
        mask = jnp.array([s % 3 != 0 for s in range(40)])
        got = _assert_compact_matches(ts, succ, pay, mask, ann, jnp.int32(250))
        new_ts = np.asarray(got[0])
        for s in range(0, 40, 3):  # masked-off rows byte-identical
            np.testing.assert_array_equal(new_ts[s], np.asarray(ts)[s])
        # every version covering a pinned ts survived
        for a in (40, 90):
            covered = (np.asarray(ts) <= a) & (a < np.asarray(succ)) \
                      & (np.asarray(ts) != -1)
            assert (new_ts[covered] != -1).all()


class TestVersionSearchKernel:
    @pytest.mark.parametrize("S,V,B", [(16, 4, 8), (128, 8, 64), (64, 16, 200)])
    def test_matches_ref(self, S, V, B):
        rng = np.random.default_rng(S + V + B)
        ts, succ, pay = _mk_slabs(rng, S, V)
        ids = jnp.array(rng.integers(0, S, B), jnp.int32)
        t = jnp.array(rng.integers(0, 220, B), jnp.int32)
        got_p, got_f = search(ts, pay, ids, t, use_kernel=True, interpret=True)
        want_p, want_f = search_ref(ts, pay, ids, t)
        np.testing.assert_array_equal(np.asarray(got_p), np.asarray(want_p))
        np.testing.assert_array_equal(np.asarray(got_f), np.asarray(want_f))


def _mk_gather_inputs(rng, S, V, M, B, max_ts=200):
    """Slabs whose payload handles are valid row indices into values[T, M]."""
    ts, succ, pay = _mk_slabs(rng, S, V, max_ts=max_ts)
    T = S * V
    pay_np = np.asarray(pay)
    remapped = np.where(pay_np != -1,
                        rng.integers(0, T, pay_np.shape).astype(np.int32), -1)
    values = jnp.array(rng.integers(0, 10_000, (T, M)), jnp.int32)
    ids = jnp.array(rng.integers(0, S, B), jnp.int32)
    t = jnp.array(rng.integers(0, max_ts + 20, B), jnp.int32)
    return ts, succ, jnp.array(remapped), values, ids, t


def _assert_gather_matches(ts, pay, values, ids, t, **kw):
    got = search_gather(ts, pay, values, ids, t,
                        use_kernel=True, interpret=True, **kw)
    want = search_gather_ref(ts, pay, values, ids, t)
    for g, w, name in zip(got, want, ("rows", "payload", "found")):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w), err_msg=name)
    return got


class TestSearchGatherFusedKernel:
    """Fused search(t) + value-row gather (DESIGN.md §12) vs its oracle."""

    @pytest.mark.parametrize("S,V,M,B", [(16, 4, 4, 8), (128, 8, 8, 64),
                                         (64, 16, 16, 200), (33, 5, 3, 17)])
    def test_matches_ref(self, S, V, M, B):
        rng = np.random.default_rng(S + V + M + B)
        ts, _, pay, values, ids, t = _mk_gather_inputs(rng, S, V, M, B)
        _assert_gather_matches(ts, pay, values, ids, t)

    def test_block_boundary(self):
        rng = np.random.default_rng(4)
        ts, _, pay, values, ids, t = _mk_gather_inputs(rng, 32, 4, 4, 70)
        _assert_gather_matches(ts, pay, values, ids, t, block_b=32)

    def test_before_first_write(self):
        """Queries below every version ts: not-found, rows EMPTY-filled."""
        rng = np.random.default_rng(6)
        ts, _, pay, values, ids, _ = _mk_gather_inputs(rng, 32, 4, 4, 16)
        t = jnp.zeros((16,), jnp.int32)
        rows, _, found = _assert_gather_matches(ts, pay, values, ids, t)
        assert not bool(np.asarray(found).any())
        assert (np.asarray(rows) == -1).all()

    def test_single_version_slots(self):
        """Exactly one version per slot: found iff t >= that version's ts,
        and the gathered row is the payload-indexed values row."""
        S, V, M = 8, 4, 4
        ts = np.full((S, V), -1, np.int32)
        pay = np.full((S, V), -1, np.int32)
        for s in range(S):
            ts[s, s % V] = 10 * (s + 1)
            pay[s, s % V] = s
        values = jnp.array(np.arange(S * M, dtype=np.int32).reshape(S, M))
        ids = jnp.arange(S, dtype=jnp.int32)
        t = jnp.array([10 * (s + 1) - (s % 2) for s in range(S)], jnp.int32)
        rows, pay_got, found = _assert_gather_matches(
            jnp.array(ts), jnp.array(pay), values, ids, t)
        want_found = np.array([s % 2 == 0 for s in range(S)])
        np.testing.assert_array_equal(np.asarray(found), want_found)
        for s in range(S):
            if want_found[s]:
                np.testing.assert_array_equal(np.asarray(rows)[s],
                                              np.asarray(values)[s])


class TestFlashPrefill:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize(
        "B,Hq,Hkv,T,D,window,softcap",
        [
            (2, 4, 2, 64, 32, 0, 0.0),      # GQA global causal
            (1, 2, 1, 128, 16, 32, 0.0),    # sliding window
            (1, 4, 4, 64, 32, 0, 50.0),     # MHA + softcap (gemma2)
            (2, 8, 2, 96, 64, 48, 30.0),    # everything at once, ragged T
        ],
    )
    def test_matches_ref(self, dtype, B, Hq, Hkv, T, D, window, softcap):
        rng = np.random.default_rng(hash((B, Hq, T, D)) % 2**31)
        q = jnp.array(rng.standard_normal((B, Hq, T, D)), dtype) * 0.5
        k = jnp.array(rng.standard_normal((B, Hkv, T, D)), dtype) * 0.5
        v = jnp.array(rng.standard_normal((B, Hkv, T, D)), dtype) * 0.5
        got = flash_attention(q, k, v, causal=True, window=window,
                              softcap=softcap, block_t=32, block_s=32)
        want = attention_ref(q, k, v, causal=True, window=window, softcap=softcap)
        atol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            atol=atol, rtol=1e-2)

    def test_block_not_dividing_seq(self):
        rng = np.random.default_rng(3)
        q = jnp.array(rng.standard_normal((1, 2, 80, 16)), jnp.float32)
        k = jnp.array(rng.standard_normal((1, 2, 80, 16)), jnp.float32)
        v = jnp.array(rng.standard_normal((1, 2, 80, 16)), jnp.float32)
        got = flash_attention(q, k, v, block_t=32, block_s=32)
        want = attention_ref(q, k, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=3e-5, rtol=1e-2)


class TestPagedDecode:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize(
        "B,Hq,Hkv,D,N,PS,MP",
        [
            (2, 4, 2, 32, 16, 8, 4),
            (4, 8, 1, 64, 32, 16, 6),   # MQA (recurrentgemma local attn)
            (1, 2, 2, 16, 8, 4, 3),
        ],
    )
    def test_matches_ref(self, dtype, B, Hq, Hkv, D, N, PS, MP):
        rng = np.random.default_rng(hash((B, Hq, D, N)) % 2**31)
        q = jnp.array(rng.standard_normal((B, Hq, D)), dtype) * 0.5
        kp = jnp.array(rng.standard_normal((N, PS, Hkv, D)), dtype) * 0.5
        vp = jnp.array(rng.standard_normal((N, PS, Hkv, D)), dtype) * 0.5
        table = jnp.array(rng.integers(0, N, (B, MP)), jnp.int32)
        lengths = jnp.array(rng.integers(1, MP * PS + 1, (B,)), jnp.int32)
        got = paged_decode(q, kp, vp, table, lengths, use_kernel=True)
        want = paged_decode_ref(q, kp, vp, table, lengths)
        atol = 3e-2 if dtype == jnp.bfloat16 else 3e-5
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            atol=atol, rtol=1e-2)

    def test_zero_length_sequence(self):
        q = jnp.ones((1, 2, 8), jnp.float32)
        kp = jnp.ones((4, 4, 2, 8), jnp.float32)
        vp = jnp.ones((4, 4, 2, 8), jnp.float32)
        table = jnp.zeros((1, 2), jnp.int32)
        lengths = jnp.array([0], jnp.int32)
        out = paged_decode(q, kp, vp, table, lengths)
        assert not bool(jnp.isnan(out).any())


class TestKernelEdgeCases:
    """Degenerate inputs every kernel must agree with its oracle on."""

    def test_compact_empty_slabs(self):
        S, V = 16, 4
        ts = jnp.full((S, V), -1, jnp.int32)
        succ = jnp.full((S, V), TS_MAX, jnp.int32)
        ann = jnp.full((4,), TS_MAX, jnp.int32)
        got = compact_needed(ts, succ, ann, jnp.int32(10), use_kernel=True,
                             interpret=True)
        want = needed_ref(ts, succ, ann, jnp.int32(10))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        assert not bool(np.asarray(got).any())   # nothing exists: nothing needed

    def test_compact_all_readers_idle(self):
        rng = np.random.default_rng(7)
        ts, succ, _ = _mk_slabs(rng, 40, 6)
        ann = jnp.full((8,), TS_MAX, jnp.int32)  # no pinned snapshots
        now = jnp.int32(150)
        got = compact_needed(ts, succ, ann, now, use_kernel=True, interpret=True)
        want = needed_ref(ts, succ, ann, now)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_version_search_before_first_write(self, seed):
        """Queries at t below every version ts must report not-found."""
        rng = np.random.default_rng(seed)
        ts, succ, pay = _mk_slabs(rng, 32, 4, max_ts=200)
        ids = jnp.array(rng.integers(0, 32, 16), jnp.int32)
        t = jnp.zeros((16,), jnp.int32)          # everything written at ts>=1
        got_p, got_f = search(ts, pay, ids, t, use_kernel=True, interpret=True)
        want_p, want_f = search_ref(ts, pay, ids, t)
        np.testing.assert_array_equal(np.asarray(got_p), np.asarray(want_p))
        np.testing.assert_array_equal(np.asarray(got_f), np.asarray(want_f))
        assert not bool(np.asarray(got_f).any())

    def test_flash_single_query_block(self):
        """T smaller than one block: masking, not padding garbage."""
        rng = np.random.default_rng(11)
        q = jnp.array(rng.standard_normal((1, 2, 17, 16)), jnp.float32)
        k = jnp.array(rng.standard_normal((1, 2, 17, 16)), jnp.float32)
        v = jnp.array(rng.standard_normal((1, 2, 17, 16)), jnp.float32)
        got = flash_attention(q, k, v, causal=True, block_t=32, block_s=32)
        want = attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=3e-5, rtol=1e-2)

    def test_paged_decode_single_page(self):
        rng = np.random.default_rng(13)
        q = jnp.array(rng.standard_normal((2, 2, 8)), jnp.float32)
        kp = jnp.array(rng.standard_normal((3, 4, 2, 8)), jnp.float32)
        vp = jnp.array(rng.standard_normal((3, 4, 2, 8)), jnp.float32)
        table = jnp.array([[1], [2]], jnp.int32)
        lengths = jnp.array([4, 2], jnp.int32)
        got = paged_decode(q, kp, vp, table, lengths, use_kernel=True)
        want = paged_decode_ref(q, kp, vp, table, lengths)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=3e-5, rtol=1e-2)


def _ssm_inputs(seed, Bt, H, P, N, G, state_dtype=jnp.bfloat16):
    k = jax.random.split(jax.random.PRNGKey(seed), 7)
    state = jax.random.normal(k[0], (Bt, N, H * P)).astype(state_dtype)
    x = jax.random.normal(k[1], (Bt, H * P))
    dt = jax.nn.softplus(jax.random.normal(k[2], (Bt, H)) - 3.0)
    A = -jnp.exp(jax.random.normal(k[3], (H,)))
    B = jax.random.normal(k[4], (Bt, G, N))
    C = jax.random.normal(k[5], (Bt, G, N))
    D = 1.0 + 0.1 * jax.random.normal(k[6], (H,))
    return state, x, dt, A, B, C, D


class TestSsmUpdateKernel:
    """The fused Mamba-2 decode update in interpret mode vs its lax path.

    Both compute the same float32 expressions; only the order of the sum
    over ``N`` may differ, so ``y`` agrees to float32 rounding (1e-5 on
    values of order 10), and the bfloat16 state to one bfloat16 rounding of
    that (relative 2**-8)."""

    @pytest.mark.parametrize("Bt,H,P,N,G", [(2, 4, 16, 16, 1),
                                            (3, 8, 16, 16, 2),
                                            (2, 64, 64, 128, 1)])
    def test_matches_ref(self, Bt, H, P, N, G):
        from repro.kernels.ssm_update.ops import ssm_update
        args = _ssm_inputs(Bt * H + G, Bt, H, P, N, G)
        y, s = ssm_update(*args, use_kernel=True, interpret=True)
        y_ref, s_ref = ssm_update(*args, use_kernel=False)
        assert s.dtype == jnp.bfloat16 and y.shape == (Bt, H * P)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   atol=1e-5, rtol=1e-6)
        np.testing.assert_allclose(np.asarray(s, np.float32),
                                   np.asarray(s_ref, np.float32),
                                   rtol=2 ** -8, atol=1e-6)

    def test_blocks_and_groups(self):
        """Several lane blocks per group, and each group's B and C."""
        from repro.kernels.ssm_update.kernel import ssm_update_pallas
        from repro.kernels.ssm_update.ref import ssm_update_ref
        Bt, H, P, N, G = 2, 8, 32, 16, 2
        state, x, dt, A, B, C, D = _ssm_inputs(4, Bt, H, P, N, G,
                                               jnp.float32)
        rows = lambda v: jnp.repeat(v, P, axis=-1)
        y, s = ssm_update_pallas(
            state, x[:, None], rows(dt)[:, None], rows(A)[None],
            rows(D)[None], B.reshape(-1, N, 1), C.reshape(-1, N, 1),
            block_lanes=64, interpret=True)
        y_ref, s_ref = ssm_update_ref(state, x, dt, A, B, C, D)
        np.testing.assert_allclose(np.asarray(y[:, 0]), np.asarray(y_ref),
                                   atol=1e-5, rtol=1e-6)
        np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref),
                                   atol=1e-5, rtol=1e-6)

    def test_ref_is_the_recurrence(self):
        """The lax path against the recurrence written per head in numpy,
        in the ``[H, P, N]`` layout."""
        from repro.kernels.ssm_update.ref import ssm_update_ref
        Bt, H, P, N, G = 2, 4, 8, 16, 2
        state, x, dt, A, B, C, D = (np.asarray(a, np.float64) for a in
                                    _ssm_inputs(7, Bt, H, P, N, G,
                                                jnp.float32))
        y, s = ssm_update_ref(*(jnp.asarray(a, jnp.float32) for a in
                                (state, x, dt, A, B, C, D)))
        S = state.reshape(Bt, N, H, P).transpose(0, 2, 3, 1)
        for b in range(Bt):
            for h in range(H):
                g = h // (H // G)
                xh = x[b, h * P:(h + 1) * P]
                Sh = (np.exp(dt[b, h] * A[h]) * S[b, h]
                      + dt[b, h] * np.outer(xh, B[b, g]))
                np.testing.assert_allclose(
                    np.asarray(y)[b, h * P:(h + 1) * P],
                    Sh @ C[b, g] + D[h] * xh, rtol=1e-5, atol=1e-5)
                np.testing.assert_allclose(
                    np.asarray(s)[b].reshape(N, H, P)[:, h].T, Sh,
                    rtol=1e-5, atol=1e-5)
