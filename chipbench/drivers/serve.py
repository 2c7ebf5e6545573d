"""A served model: closed-loop waves of greedy requests through
``MVServeEngine``, with snapshot readers pinned mid-decode.

One general generator for every served mix: the mix's data file gives the
wave's batch, prompt length, tokens per request, cache length, the GC
policy and slab depth, and the decode steps after which reader lanes pin.
The configuration gives the model.

A wave is ``batch`` requests due at once: one prefill of random prompts
from the seed (its last position gives each request's first token), then
``new_tokens - 1`` decode steps.  Lane ``L`` pins after decode step
``pin_at[L]`` and holds its snapshot to the wave's end.  A wave starts
while the window has time left and always runs to its end.

What is compared for ``correct``, after the window: for a sample of the
window's requests drawn from the seed, the widest gap by which a served
token's logit lies below the best logit of the float32 reference run over
the prompt and the served tokens (`chipbench.reference.model`); and every
pinned reader's lengths, at its pin and at the wave's end, against the
lengths the schedule implies.
"""
from __future__ import annotations

import dataclasses
import time
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import harness
from chipbench.reference import model as ref


def model_config(cfg):
    """The system's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import ModelConfig
    if cfg["hidden_act"] != "silu":
        raise ValueError(f"{cfg['name']}: only SwiGLU decoders are served")
    return ModelConfig(
        name=cfg["name"], family="dense",
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        head_dim=cfg["head_dim"], rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]), act="silu", gated_mlp=True,
        tie_embeddings=bool(cfg.get("tie_word_embeddings", True)))


def wave_prompts(seed: int, vocab: int, batch: int, prompt_len: int):
    """The prompts of each wave in turn, from the seed: token ids uniform
    over the vocabulary, every wave the same size."""
    rng = np.random.default_rng(seed)
    while True:
        yield rng.integers(0, vocab, (batch, prompt_len), np.int32)


def run(r: harness.Run) -> None:
    from repro.configs.base import SHAPES, RunConfig
    from repro.core.telemetry import GCConfig
    from repro.models import transformer as tf
    from repro.serve.engine import MVServeEngine

    cfg, mix = r.config, r.traffic
    mcfg = model_config(cfg)
    batch, plen = int(mix["batch"]), int(mix["prompt_len"])
    new, max_len = int(mix["new_tokens"]), int(mix["max_len"])
    pin_at: List[int] = [int(x) for x in mix["pin_at"]]
    gc = GCConfig(policy=mix["policy"],
                  versions_per_slot=int(mix["versions_per_slot"]),
                  reader_lanes=int(mix["reader_lanes"]))
    run_cfg = RunConfig(model=mcfg, shape=SHAPES["decode_32k"], gc=gc,
                        dtype=cfg["torch_dtype"],
                        param_dtype=cfg["torch_dtype"])

    params = ref.make_weights(cfg, r.seed, jnp.bfloat16)
    want = jax.eval_shape(lambda k: tf.init_params(mcfg, k, jnp.bfloat16),
                          jax.random.PRNGKey(0))
    got = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                       params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            a.shape != b.shape for a, b in zip(jax.tree.leaves(want),
                                               jax.tree.leaves(got))):
        raise ValueError("the benchmark's weights do not fit the system's "
                         "parameter tree")
    eng = MVServeEngine(mcfg, run_cfg, params, batch=batch, max_len=max_len,
                        dtype=jnp.dtype(cfg["torch_dtype"]))
    prompts = wave_prompts(r.seed, cfg["vocab_size"], batch, plen)

    # warm every program a wave runs: prefill, decode, pin, read, unpin
    warm = jnp.asarray(next(prompts))
    eng.prefill(warm)
    jax.block_until_ready(eng.step())
    t = eng.pin(0)
    jax.block_until_ready(eng.lengths_at(t))
    eng.unpin(0)
    jax.block_until_ready(eng.state.cache_len)

    waves = []
    gaps: List[float] = []
    ctx: List[int] = []             # each decode step's context length
    ttft: List[float] = []
    reader_bad = 0
    retry_failed = 0
    r.setup_done()
    with harness.Window(r) as w:
        while w.elapsed() < r.seconds:
            p_host = next(prompts)
            due = time.perf_counter()
            with harness.span("chipbench.prefill"):
                eng.prefill(jnp.asarray(p_host))
                first = jax.block_until_ready(eng.state.last_tokens)
            ttft.append(time.perf_counter() - due)
            toks, pins = [first], {}
            for i in range(new - 1):
                t0 = time.perf_counter()
                with harness.span("chipbench.decode"):
                    toks.append(jax.block_until_ready(eng.step()))
                gaps.append(time.perf_counter() - t0)
                ctx.append(plen + i + 1)
                retry_failed += eng.last_stats.get("retry_failed", 0)
                for lane, at in enumerate(pin_at):
                    if at == i:
                        with harness.span("chipbench.reader"):
                            tp = eng.pin(lane)
                            pins[lane] = (i, tp, eng.lengths_at(tp))
            with harness.span("chipbench.reader"):
                for lane, (i, tp, at_pin) in pins.items():
                    at_end = eng.lengths_at(tp)
                    want_len = plen + i + 1
                    reader_bad += int((np.asarray(at_pin) != want_len).sum()
                                      + (np.asarray(at_end) != want_len).sum())
                    eng.unpin(lane)
            waves.append((p_host, np.concatenate(
                [np.asarray(x) for x in toks], axis=1)))
    n_req = batch * len(waves)
    r.attempted, r.failed = n_req, 0
    r.obs.update({
        "window_s": w.elapsed(),
        "tokens": n_req * new,
        "gaps_s": gaps,
        "ttft_s": [x for x in ttft for _ in range(batch)],
        "decode_ctx": ctx,
        "prefills": len(waves),
        "shapes": {"batch": batch, "prompt_len": plen, "slots": batch,
                   "versions": gc.versions_per_slot,
                   "lanes": gc.reader_lanes},
    })
    r.obs["device"] = harness.device_info(jax.devices()[:r.chips])

    # the sample the reference checks, drawn from the seed: whole requests
    # at rows spread evenly over the batch, each from a random wave
    k = min(int(mix["check_requests"]), batch)
    rng = np.random.default_rng(r.seed ^ 0x5EED)
    rows = (int(rng.integers(batch)) + np.arange(k) * batch // k) % batch
    picks = rng.integers(len(waves), size=k)
    sample = [(waves[w][0][row], waves[w][1][row])
              for w, row in zip(picks, rows)]
    r.obs["sample"] = sample
    del eng, params, waves
    gap = widest_gap(cfg, r.seed, sample)
    r.check("reader_lengths_wrong", reader_bad, 0)
    r.check("descriptor_retries_failed", retry_failed, 0)
    r.check("served_logit_gap", gap, float(r.limits["served_logit_gap"]))


def control(r: harness.Run) -> harness.Run:
    """The control of a finished run: its checks, with the served tokens'
    gap replaced by that of the float8 (e4m3) reference's own picks, held
    to the same limit.  A sound limit makes it not ``correct``."""
    ctl = dataclasses.replace(r, checks=dict(r.checks))
    gap = widest_gap(r.config, r.seed, r.obs["sample"], quant="fp8")
    ctl.check("served_logit_gap", gap, float(r.limits["served_logit_gap"]))
    return ctl


def widest_gap(cfg, seed: int, sample, quant=None) -> float:
    """The reference over each sampled request's prompt and served tokens:
    the widest gap by which a served token's logit lies below the
    reference's best at its position.  With ``quant`` the reference in
    that precision stands in for the served tokens: at each position it
    picks its own best, and the gap of that pick is read."""
    weights = ref.make_weights(cfg, seed, jnp.float32)
    worst = 0.0
    for prompt, served in sample:
        seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
        logits = ref.forward(weights, cfg, jnp.asarray(seq), len(served))
        if quant is None:
            pick = jnp.asarray(served.astype(np.int32))
        else:
            ctl = ref.forward(weights, cfg, jnp.asarray(seq), len(served),
                              quant=quant)
            pick = jnp.argmax(ctl, axis=-1)
        chosen = jnp.take_along_axis(logits, pick[:, None], axis=1)[:, 0]
        worst = max(worst, float(jnp.max(logits.max(axis=-1) - chosen)))
    return worst
