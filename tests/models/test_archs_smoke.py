"""Per-architecture smoke tests on reduced configs: one forward + one train
gradient step on CPU, asserting output shapes and no NaNs; plus a
prefill/decode-vs-forward consistency check for cacheable archs."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

jax.config.update("jax_platform_name", "cpu")

from repro.configs import ARCHS, list_archs, reduced_config
from repro.models import transformer as tf


def make_batch(cfg, rng, B=2, T=32):
    tokens = jnp.array(rng.integers(0, cfg.vocab_size, (B, T)), jnp.int32)
    batch = {"tokens": tokens}
    if cfg.frontend != "none":
        Nf = cfg.frontend_tokens if cfg.encoder_layers else cfg.frontend_tokens
        batch["frontend"] = jnp.array(
            rng.standard_normal((B, max(Nf, 4), cfg.d_model)) * 0.02, jnp.float32)
    return batch


@pytest.mark.parametrize("arch", list_archs())
def test_forward_and_grad_step(arch):
    cfg = reduced_config(arch)
    rng = np.random.default_rng(0)
    params = tf.init_params(cfg, jax.random.PRNGKey(0))
    batch = make_batch(cfg, rng)

    loss, metrics = tf.loss_fn(params, cfg, batch, remat=False)
    assert jnp.isfinite(loss), f"{arch}: non-finite loss {loss}"

    grads = jax.grad(lambda p: tf.loss_fn(p, cfg, batch, remat=True)[0])(params)
    flat = jax.tree.leaves(grads)
    assert all(jnp.isfinite(g).all() for g in flat), f"{arch}: NaN/inf grads"
    # at least 99% of param tensors receive nonzero gradient signal
    nz = sum(bool(jnp.any(g != 0)) for g in flat)
    assert nz >= 0.8 * len(flat), f"{arch}: too many dead grads ({nz}/{len(flat)})"


@pytest.mark.parametrize("arch", list_archs())
def test_logit_shapes(arch):
    cfg = reduced_config(arch)
    rng = np.random.default_rng(1)
    params = tf.init_params(cfg, jax.random.PRNGKey(1))
    batch = make_batch(cfg, rng, B=2, T=16)
    logits, aux = tf.forward(params, cfg, batch["tokens"],
                             frontend_embeds=batch.get("frontend"), remat=False)
    Nf = 0
    if cfg.frontend != "none" and not cfg.encoder_layers:
        Nf = batch["frontend"].shape[1]
    assert logits.shape == (2, 16 + Nf, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all())


@pytest.mark.parametrize("arch", list_archs())
def test_prefill_decode_matches_forward(arch):
    """prefill(prompt) then decode one token == forward(prompt + token)."""
    cfg = reduced_config(arch)
    if cfg.frontend != "none" and not cfg.encoder_layers:
        pytest.skip("vlm prefix handled in forward test")
    rng = np.random.default_rng(2)
    params = tf.init_params(cfg, jax.random.PRNGKey(2))
    B, T = 2, 16
    tokens = jnp.array(rng.integers(0, cfg.vocab_size, (B, T + 1)), jnp.int32)
    fe = None
    if cfg.encoder_layers:
        fe = jnp.array(rng.standard_normal(
            (B, max(cfg.encoder_tokens, 4), cfg.d_model)) * 0.02, jnp.float32)

    # teacher-forced forward over the full sequence
    logits_full, _ = tf.forward(params, cfg, tokens, frontend_embeds=fe,
                                remat=False)

    # prefill T tokens, then decode token T
    cache = tf.init_cache(cfg, B, cache_len=T + 8, dtype=jnp.float32)
    last, cache, lens = tf.prefill(params, cfg, tokens[:, :T], cache,
                                   frontend_embeds=fe)
    enc_out = None
    if cfg.encoder_layers:
        enc_out = tf._run_encoder(params, cfg, fe)
    step_logits, cache = tf.decode_step(params, cfg, tokens[:, T:T + 1],
                                        cache, lens, enc_out=enc_out)

    np.testing.assert_allclose(
        np.asarray(last[:, -1], np.float32),
        np.asarray(logits_full[:, T - 1], np.float32),
        atol=2e-3, rtol=2e-3,
        err_msg=f"{arch}: prefill last-logit mismatch")
    np.testing.assert_allclose(
        np.asarray(step_logits[:, 0], np.float32),
        np.asarray(logits_full[:, T], np.float32),
        atol=2e-3, rtol=2e-3,
        err_msg=f"{arch}: decode-step logit mismatch")


def test_param_counts_full_configs():
    """Full configs instantiate *analytically* close to their nameplate size
    (no allocation — just the formula)."""
    expect = {
        "xlstm-125m": (0.06e9, 0.22e9),
        "granite-moe-1b-a400m": (0.8e9, 1.6e9),
        "deepseek-moe-16b": (14e9, 20e9),
        "internvl2-2b": (1.5e9, 2.6e9),
        "minitron-4b": (3.5e9, 5.5e9),
        "qwen2.5-32b": (28e9, 36e9),
        "starcoder2-7b": (6e9, 8.5e9),
        "gemma2-2b": (2.0e9, 3.5e9),
        "whisper-tiny": (0.02e9, 0.06e9),
        "recurrentgemma-9b": (7.5e9, 11e9),
        "granite-4.0-h-micro": (3.0e9, 3.4e9),
    }
    for name, (lo, hi) in expect.items():
        n = ARCHS[name].param_count()
        assert lo <= n <= hi, f"{name}: analytic count {n/1e9:.2f}B not in [{lo/1e9}, {hi/1e9}]B"


def test_mlstm_chunkwise_equals_recurrent():
    """The chunk-parallel train path must equal the step recurrence exactly."""
    from repro.models import mlstm as m
    cfg = reduced_config("xlstm-125m", mlstm_chunk=8)
    key = jax.random.PRNGKey(3)
    params = m.init_mlstm(key, cfg)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 32, cfg.d_model)) * 0.5
    out_c, st_c = m.mlstm_chunkwise(params, cfg, x)
    out_r, st_r = m.mlstm_decode(params, cfg, x, m.mlstm_init_state(cfg, 2))
    np.testing.assert_allclose(np.asarray(out_c), np.asarray(out_r),
                               atol=1e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(st_c.C), np.asarray(st_r.C),
                               atol=1e-4, rtol=1e-3)


def test_rglru_scan_equals_stepwise():
    from repro.models import rglru as r
    cfg = reduced_config("recurrentgemma-9b")
    params = r.init_rglru(jax.random.PRNGKey(5), cfg)
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 16, cfg.d_model)) * 0.5
    out_scan, st_scan = r.rglru(params, cfg, x)
    # stepwise
    st = r.rglru_init_state(cfg, 2)
    outs = []
    for t in range(16):
        o, st = r.rglru_decode(params, cfg, x[:, t:t+1], st)
        outs.append(o)
    out_step = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(out_scan), np.asarray(out_step),
                               atol=1e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(st_scan.h), np.asarray(st.h),
                               atol=1e-4, rtol=1e-3)


def test_mamba2_chunked_equals_stepwise():
    """The chunked SSD prompt pass and the one-token decode step agree: a
    prompt of 20 (two chunks of 8 and a padded one) against 20 decode steps
    from a zero state, on every output and on the final state and window."""
    from repro.models import mamba2 as m
    cfg = reduced_config("granite-4.0-h-micro")
    params = m.init_mamba2(jax.random.PRNGKey(7), cfg)
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 20, cfg.d_model)) * 0.5
    out_c, st_c = m.mamba2(params, cfg, x)
    st = m.mamba2_init_state(cfg, 2, jnp.float32)
    outs = []
    for t in range(20):
        o, st = m.mamba2_decode(params, cfg, x[:, t:t + 1], st)
        outs.append(o)
    np.testing.assert_allclose(np.asarray(out_c),
                               np.asarray(jnp.concatenate(outs, axis=1)),
                               atol=1e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(st_c.ssm), np.asarray(st.ssm),
                               atol=1e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(st_c.conv), np.asarray(st.conv),
                               atol=1e-6)
