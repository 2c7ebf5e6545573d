"""The served Mamba-2/attention hybrid against the benchmark's float32
reference (`chipbench.reference.hybrid`), on the CPU at a small size of the
published shape: d 64, 4 Mamba heads of 16, d_state 16, chunks of 8, one
whole period of 10 layers (5 Mamba, attention, 4 Mamba), seeded random
weights.

The system runs here in float32 (weights and cache), so what separates it
from the reference is the order of its arithmetic: the chunked SSD sums
each chunk's products in blocks where the reference steps token by token.
Tolerances are set from that: over the 10-layer pass, with logits of
order 0.01 and states of order 1, the two differ by at most 7e-7 (outputs,
states, logits); 1e-5 leaves over ten times that, and lies a thousand
times below what a dropped term moves (the faults in
``test_chipbench_hybrid.py`` move the served logits by 0.01 and more)."""
from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import counts_hybrid
from chipbench.drivers import serve_hybrid
from chipbench.reference import hybrid as ref

jax.config.update("jax_platform_name", "cpu")

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SEED = 2 ** 33 + 11
ATOL = 1e-5        # float32 summation order over a 10-layer pass; see above


def _json(path):
    with open(path) as f:
        return json.load(f)


TINY = _json(os.path.join(DATA, "tiny-hybrid.json"))
GRANITE = _json(os.path.join(ROOT, "chipbench", "configs",
                             "granite-4.0-h-micro.json"))


@pytest.fixture(scope="module")
def system():
    return serve_hybrid.model_config(TINY), ref.make_weights(
        TINY, SEED, jnp.float32)


def _tokens(B, T, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, TINY["vocab_size"], (B, T)),
                       jnp.int32)


def test_chunked_prefill_matches_sequential_reference(system):
    """The system's chunked SSD forward pass (20 tokens: two whole chunks
    of 8 and a padded one) against the reference's token-by-token
    recurrence, on every position's logits."""
    from repro.models import transformer as tf
    cfg, params = system
    toks = _tokens(2, 20)
    got, _ = tf.forward(params, cfg, toks, remat=False)
    want = ref.forward(TINY, SEED, toks, 20)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=ATOL,
                               rtol=0)


def test_chunked_final_state_matches_sequential_reference(system):
    """One Mamba layer's state after a 20-token prompt, chunked against
    sequential (the system holds it as ``[B, N, H*P]``)."""
    from repro.models import mamba2
    cfg, params = system
    mixer = jax.tree.map(lambda a: a[0], params["sb"]["l0"]["mixer"])
    h = jax.random.normal(jax.random.PRNGKey(3), (2, 20, cfg.d_model))
    out, st = mamba2.mamba2(mixer, cfg, h)
    with jax.default_matmul_precision("highest"):
        want_out, want_st = ref._mamba(h, mixer, TINY, lambda w, axis: w)
    B, N, HP = st.ssm.shape
    got_st = np.asarray(st.ssm).reshape(B, N, cfg.mamba_heads, -1)
    np.testing.assert_allclose(got_st.transpose(0, 2, 3, 1),
                               np.asarray(want_st), atol=ATOL, rtol=0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want_out),
                               atol=ATOL, rtol=0)
    # the conv window is the prompt's last d_conv - 1 pre-conv inputs
    z, xbc, _ = mamba2._in_proj(mixer, cfg, h)
    np.testing.assert_allclose(np.asarray(st.conv), np.asarray(xbc[:, -3:]),
                               atol=1e-6)


def test_engine_prefill_then_decode_matches_reference(system):
    """Prefill and 10 decode steps through ``MVServeEngine`` (its prefill,
    step, pin, read and unpin programs), each step's logits against the
    reference's full forward pass over the prompt and the served tokens."""
    from repro.configs.base import SHAPES, RunConfig
    from repro.core.telemetry import GCConfig
    from repro.models import transformer as tf
    from repro.serve.engine import MVServeEngine
    cfg, params = system
    run = RunConfig(model=cfg, shape=SHAPES["decode_32k"],
                    gc=GCConfig(versions_per_slot=16, reader_lanes=4))
    B, T0, steps = 3, 13, 10
    eng = MVServeEngine(cfg, run, params, batch=B, max_len=T0 + steps,
                        dtype=jnp.float32)
    prompt = _tokens(B, T0, seed=1)
    eng.prefill(prompt)
    first, _, _ = tf.prefill(params, cfg, prompt,
                             tf.init_cache(cfg, B, T0 + steps, jnp.float32))
    logits, served = [first[:, -1]], [eng.state.last_tokens]
    t = eng.pin(0)
    for _ in range(steps):
        s = eng.state
        step_logits, _ = tf.decode_step(s.params, cfg, s.last_tokens,
                                        s.cache, s.cache_len)
        logits.append(step_logits[:, -1])
        served.append(eng.step())
    assert np.asarray(eng.lengths_at(t)).tolist() == [T0] * B
    eng.unpin(0)
    served = np.concatenate([np.asarray(x) for x in served], axis=1)
    seqs = jnp.concatenate([prompt, jnp.asarray(served[:, :-1])], axis=1)
    want = ref.forward(TINY, SEED, seqs, steps + 1)
    got = jnp.stack(logits, axis=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=ATOL,
                               rtol=0)
    # the served tokens are each step's greedy pick
    np.testing.assert_array_equal(served, np.asarray(got.argmax(-1)))


def test_served_config_is_the_registry_entry():
    from repro.configs import ARCHS
    assert serve_hybrid.model_config(GRANITE) == ARCHS["granite-4.0-h-micro"]


def test_weights_fit_the_system_tree():
    from repro.models import transformer as tf
    cfg = serve_hybrid.model_config(GRANITE)
    want = jax.eval_shape(lambda k: tf.init_params(cfg, k, jnp.bfloat16),
                          jax.random.PRNGKey(0))
    got = jax.eval_shape(lambda: ref.make_weights(GRANITE, 1))
    assert jax.tree.structure(want) == jax.tree.structure(got)
    assert [(a.shape, a.dtype) for a in jax.tree.leaves(want)] == \
        [(a.shape, a.dtype) for a in jax.tree.leaves(got)]


def test_counts_granite_by_hand():
    # Mamba: in_proj 2048 x (4096 + 4352 + 64), conv 4 x 4352 taps and
    # out_proj 4096 x 2048 multiply every token; attention 2048 x 64 x
    # (32 + 2 * 8) and 32 x 64 x 2048; SwiGLU 3 x 2048 x 8192
    mamba_mm = 2048 * 8512 + 4 * 4352 + 4096 * 2048
    attn_mm = 2048 * 64 * 48 + 32 * 64 * 2048
    mlp = 3 * 2048 * 8192
    assert counts_hybrid.matmul_params(GRANITE, "mamba") == mamba_mm + mlp
    assert counts_hybrid.matmul_params(GRANITE, "attention") == attn_mm + mlp
    # plus conv bias, dt_bias, A_log, D, the gated norm, two RMSNorms
    mamba = mamba_mm + mlp + 4352 + 3 * 64 + 4096 + 2 * 2048
    attn = attn_mm + mlp + 2 * 2048
    p = counts_hybrid.params(GRANITE)
    assert p == {"layers": 36 * mamba + 4 * attn + 2048,
                 "embed": 100352 * 2048}
    assert sum(p.values()) == 3191396096
    # the registry's count leaves out the final norm
    from repro.configs import ARCHS
    assert ARCHS["granite-4.0-h-micro"].param_count() == 3191396096 - 2048
    # 36 x 64 x 64 x 128 bf16 state per sequence: 37.7 MB, 1.21 GB at 32
    assert counts_hybrid.ssm_state_bytes(GRANITE, 1) == 37748736
    assert counts_hybrid.ssm_state_bytes(GRANITE, 32) == 1207959552
    assert counts_hybrid.conv_window_bytes(GRANITE, 1) == 36 * 3 * 4352 * 2
    assert counts_hybrid.kv_bytes_per_token(GRANITE) == 8192
    # one layer's update at batch 32: the state read and written, x, dt,
    # B, C and y per sequence in float32, A and D once
    assert counts_hybrid.ssm_update_bytes(GRANITE, 32) == \
        2 * 32 * 64 * 64 * 128 * 2 + 32 * (4096 + 64 + 256 + 4096) * 4 \
        + 2 * 64 * 4
    want = ((p["layers"] + p["embed"] + 32 * 2048) * 2
            + 2 * (1207959552 + 32 * 36 * 3 * 4352 * 2)
            + 8192 * 1025 * 32 + 3 * 32 * 16 * 4 + 8 * 4)
    assert counts_hybrid.decode_min_bytes(GRANITE, [1025] * 32, 32, 16,
                                          8) == want
    # decode: 2 x matmul weights and the head per token, the state's update
    # and read-out, attention over each context in 4 layers
    per_token = (2 * (36 * (mamba_mm + mlp) + 4 * (attn_mm + mlp))
                 + 4 * 36 * 64 * 64 * 128 + 2 * 100352 * 2048)
    assert counts_hybrid.decode_flops(GRANITE, [1025] * 32) == \
        32 * per_token + 4 * 4 * 32 * 64 * 1025 * 32
    # prefill of 1024: four whole chunks of 256 per Mamba layer
    chunk = 2 * 256 * 257 / 2 * (128 + 4096) + 4 * 256 * 64 * 64 * 128
    assert counts_hybrid.prefill_flops(GRANITE, 1, 1024) == pytest.approx(
        2 * (36 * (mamba_mm + mlp) + 4 * (attn_mm + mlp)) * 1024
        + 4 * 4 * 32 * 64 * 1024 * 1025 / 2 + 36 * 4 * chunk
        + 2 * 100352 * 2048)


def test_space_reports_state_bytes_by_kind(system):
    from repro.configs.base import SHAPES, RunConfig
    from repro.serve.engine import MVServeEngine
    cfg, params = system
    run = RunConfig(model=cfg, shape=SHAPES["decode_32k"])
    eng = MVServeEngine(cfg, run, params, batch=3, max_len=40,
                        dtype=jnp.bfloat16)
    sp = eng.space()
    # 9 Mamba layers: 16 x 64 state and a 3 x 96 window; 1 attention
    # layer: K and V of 2 heads x 16 over 40 positions
    assert sp["recurrent_state_bytes"] == 9 * 3 * 16 * 64 * 2
    assert sp["conv_window_bytes"] == 9 * 3 * 3 * 96 * 2
    assert sp["kv_cache_bytes"] == 2 * 3 * 40 * 2 * 16 * 2
