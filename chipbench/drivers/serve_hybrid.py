"""A served Mamba-2/attention hybrid: closed-loop waves of greedy requests
through ``MVServeEngine``, with snapshot readers pinned mid-decode.

The same waves, readers and checks as `chipbench.drivers.serve`, for a
``granitemoehybrid`` configuration: the cache holds each sequence's
recurrent state and conv window in the Mamba layers beside the K/V of the
attention layers.  The mix gives the wave's batch, prompt length, tokens
per request, cache length, the GC policy and slab depth, and the decode
steps after which reader lanes pin.

What is compared for ``correct``, after the window: for a sample of the
window's requests drawn from the seed, the widest gap by which a served
token's logit lies below the best logit of the float32 reference run over
the prompt and the served tokens (`chipbench.reference.hybrid`, which runs
the recurrence token by token); and every pinned reader's lengths, at its
pin and at the wave's end, against the lengths the schedule implies.  The
engine's cache bytes by kind of state (``space()``) are printed to
standard error.
"""
from __future__ import annotations

import dataclasses
import json
import sys
import time
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import harness
from chipbench.drivers.serve import wave_prompts
from chipbench.reference import hybrid as ref


def model_config(cfg):
    """The system's ``ModelConfig`` for a ``granitemoehybrid``
    configuration file."""
    from repro.configs.base import ModelConfig
    refused = {
        "hidden_act": cfg["hidden_act"] != "silu",
        "num_local_experts": cfg["num_local_experts"] != 0,
        "position_embedding_type": cfg["position_embedding_type"] != "nope",
        "attention_bias": cfg["attention_bias"],
        "mamba_proj_bias": cfg["mamba_proj_bias"],
        "mamba_conv_bias": not cfg["mamba_conv_bias"],
    }
    if any(refused.values()):
        raise ValueError(f"{cfg['name']}: not served: "
                         f"{[k for k, v in refused.items() if v]}")
    p = ref.period(cfg)
    kinds = {"mamba": "mamba2", "attention": "attn"}
    d, nq = cfg["hidden_size"], cfg["num_attention_heads"]
    return ModelConfig(
        name=cfg["name"], family="hybrid",
        num_layers=cfg["num_hidden_layers"], d_model=d, num_heads=nq,
        num_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["shared_intermediate_size"], vocab_size=cfg["vocab_size"],
        head_dim=d // nq,
        layer_pattern=tuple(kinds[k] for k in ref.layer_kinds(cfg)[:p]),
        rope=False, mamba_heads=cfg["mamba_n_heads"],
        mamba_head_dim=cfg["mamba_d_head"],
        mamba_d_state=cfg["mamba_d_state"],
        mamba_groups=cfg["mamba_n_groups"],
        mamba_chunk=cfg["mamba_chunk_size"], conv_width=cfg["mamba_d_conv"],
        embed_mult=float(cfg["embedding_multiplier"]),
        residual_mult=float(cfg["residual_multiplier"]),
        logits_div=float(cfg["logits_scaling"]),
        attn_scale=float(cfg["attention_multiplier"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]))


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
        "space": eng.space(),
    })
    r.obs["device"] = harness.device_info(jax.devices()[:r.chips])
    print("space " + json.dumps(r.obs["space"]), file=sys.stderr)

    # the sample the reference checks, drawn from the seed: whole requests
    # at rows spread evenly over the batch, each from a random wave
    k = min(int(mix["check_requests"]), batch)
    rng = np.random.default_rng(r.seed ^ 0x5EED)
    rows = (int(rng.integers(batch)) + np.arange(k) * batch // k) % batch
    picks = rng.integers(len(waves), size=k)
    r.obs["sample"] = [(waves[w][0][row], waves[w][1][row])
                       for w, row in zip(picks, rows)]
    del eng, params, waves
    gap = widest_gap(cfg, r.seed, r.obs["sample"])
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
    """The reference over the sampled requests (prompt and served tokens,
    all of one length, in one batch): the widest gap by which a served
    token's logit lies below the reference's best at its position.  With
    ``quant`` the reference in that precision stands in for the served
    tokens: at each position it picks its own best, and the gap of that
    pick is read."""
    seqs = jnp.asarray(np.stack([np.concatenate([p, s[:-1]])
                                 for p, s in sample]).astype(np.int32))
    last = len(sample[0][1])
    logits = ref.forward(cfg, seed, seqs, last)
    if quant is None:
        pick = jnp.asarray(np.stack([s for _, s in sample]).astype(np.int32))
    else:
        pick = jnp.argmax(ref.forward(cfg, seed, seqs, last, quant=quant),
                          axis=-1)
    chosen = jnp.take_along_axis(logits, pick[..., None], axis=-1)[..., 0]
    return float(jnp.max(logits.max(axis=-1) - chosen))
