"""TransformerLM: the composable model covering all 10 assigned archs.

Depth structure: ``layer_pattern`` is cycled ``pattern_repeats`` times via
``lax.scan`` over *superblocks* (stacked params, one scan step applies the
whole pattern once) with optional per-superblock remat; any remainder layers
(pattern not dividing depth, e.g. recurrentgemma's 38 = 12*3 + 2) run
unrolled.  Scan keeps HLO size depth-independent — essential for compiling
qwen2.5-32b under 512 fake devices on one CPU.

Enc-dec (whisper): a bidirectional encoder stack over precomputed frame
embeddings; decoder blocks grow cross-attention sublayers.
VLM (internvl2): precomputed patch embeddings are prefixed to the token
embeddings; labels are masked over the prefix.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.blocks import block_apply, init_block, init_block_cache
from repro.models.common import (constrain_batch, cross_entropy_loss,
                                 embed_init, rms_norm, softcap)


def _pattern(cfg: ModelConfig) -> Tuple[str, ...]:
    return cfg.layer_pattern


def _is_encdec(cfg: ModelConfig) -> bool:
    return cfg.encoder_layers > 0


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------
def init_params(cfg: ModelConfig, key, dtype=jnp.float32) -> Dict[str, Any]:
    pat = _pattern(cfg)
    R, tail = cfg.pattern_repeats, cfg.tail_layers
    keys = jax.random.split(key, 8)
    cross = _is_encdec(cfg)

    def init_superblock(k):
        ks = jax.random.split(k, len(pat))
        return {f"l{i}": init_block(ks[i], cfg, kind, dtype, cross=cross)
                for i, kind in enumerate(pat)}

    sb_keys = jax.random.split(keys[0], R)
    params: Dict[str, Any] = {
        "embed": embed_init(keys[1], (cfg.vocab_size, cfg.d_model), dtype),
        "sb": jax.vmap(init_superblock)(sb_keys),
        "final_norm": jnp.zeros((cfg.d_model,), dtype),
    }
    if tail:
        tkeys = jax.random.split(keys[2], tail)
        params["tail"] = [
            init_block(tkeys[i], cfg, pat[i % len(pat)], dtype, cross=cross)
            for i in range(tail)
        ]
    if not cfg.tie_embeddings:
        params["unembed"] = embed_init(keys[3], (cfg.vocab_size, cfg.d_model), dtype)
    if _is_encdec(cfg):
        enc_cfg = dataclasses.replace(cfg, num_experts=0, post_norms=False)

        def init_enc_block(k):
            return {"l0": init_block(k, enc_cfg, "attn", dtype, cross=False)}

        ekeys = jax.random.split(keys[4], cfg.encoder_layers)
        params["encoder"] = {
            "sb": jax.vmap(init_enc_block)(ekeys),
            "final_norm": jnp.zeros((cfg.d_model,), dtype),
        }
    return params


def _embed(params, cfg: ModelConfig, tokens) -> jax.Array:
    x = params["embed"][tokens]
    if cfg.embed_scale:
        x = x * jnp.sqrt(jnp.asarray(cfg.d_model, x.dtype))
    if cfg.embed_mult != 1.0:
        x = x * jnp.asarray(cfg.embed_mult, x.dtype)
    return x


def _logits(params, cfg: ModelConfig, x) -> jax.Array:
    """Final norm, the output head, then the logit divisor or softcap."""
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    unembed = params.get("unembed", params["embed"])
    logits = jnp.einsum("btd,vd->btv", x, unembed)
    if cfg.logits_div != 1.0:
        logits = logits / jnp.asarray(cfg.logits_div, logits.dtype)
    if cfg.final_softcap > 0:
        logits = softcap(logits.astype(jnp.float32), cfg.final_softcap)
    return logits


def param_count(params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))


# ---------------------------------------------------------------------------
# encoder (whisper frames / any bidirectional stack)
# ---------------------------------------------------------------------------
def _run_encoder(params, cfg: ModelConfig, enc_x: jax.Array) -> jax.Array:
    B, S, d = enc_x.shape
    # fixed sinusoidal positions for the frame sequence
    pos = jnp.arange(S)
    half = d // 2
    freqs = jnp.exp(-jnp.log(10_000.0) * jnp.arange(half) / half)
    ang = pos[:, None] * freqs[None, :]
    pe = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], -1).astype(enc_x.dtype)
    x = enc_x + pe[None]
    positions = jnp.broadcast_to(pos[None], (B, S)).astype(jnp.int32)
    enc_cfg = dataclasses.replace(cfg, num_experts=0, post_norms=False)

    def body(carry, sbp):
        h, _, _ = block_apply(sbp["l0"], enc_cfg, "attn", carry, positions,
                              causal=False)
        return h, None

    x, _ = jax.lax.scan(body, x, params["encoder"]["sb"])
    return rms_norm(x, params["encoder"]["final_norm"], cfg.norm_eps)


# ---------------------------------------------------------------------------
# forward (train / teacher-forced)
# ---------------------------------------------------------------------------
def forward(
    params,
    cfg: ModelConfig,
    tokens: jax.Array,                       # i32[B, T_text]
    *,
    frontend_embeds: Optional[jax.Array] = None,   # [B, Nf, d] (vlm/audio enc)
    remat: bool = True,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (logits [B, T_total, V], aux_loss)."""
    B, Tt = tokens.shape
    x = _embed(params, cfg, tokens)

    enc_out = None
    if _is_encdec(cfg):
        assert frontend_embeds is not None, "enc-dec needs frame embeddings"
        enc_out = _run_encoder(params, cfg, frontend_embeds)
    elif cfg.frontend != "none" and frontend_embeds is not None:
        x = jnp.concatenate([frontend_embeds.astype(x.dtype), x], axis=1)

    T = x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(T)[None], (B, T)).astype(jnp.int32)
    pat = _pattern(cfg)

    x = constrain_batch(x)

    def superblock(carry, sbp):
        h, aux = carry
        for i, kind in enumerate(pat):
            h, _, a = block_apply(sbp[f"l{i}"], cfg, kind, h, positions,
                                  enc_out=enc_out)
            aux = aux + a
        return (constrain_batch(h), aux), None

    sb_fn = jax.checkpoint(superblock) if remat else superblock
    (x, aux), _ = jax.lax.scan(sb_fn, (x, jnp.float32(0)), params["sb"])
    for i, bp in enumerate(params.get("tail", [])):
        x, _, a = block_apply(bp, cfg, pat[i % len(pat)], x, positions,
                              enc_out=enc_out)
        aux = aux + a

    logits = _logits(params, cfg, x)
    return logits, aux


def loss_fn(params, cfg: ModelConfig, batch: Dict[str, jax.Array],
            remat: bool = True) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Next-token CE over the text region (frontend prefix masked)."""
    tokens = batch["tokens"]
    logits, aux = forward(params, cfg, tokens,
                          frontend_embeds=batch.get("frontend"), remat=remat)
    Nf = 0
    if cfg.frontend != "none" and not _is_encdec(cfg) and "frontend" in batch:
        Nf = batch["frontend"].shape[1]
    text_logits = logits[:, Nf:, :]
    pred = text_logits[:, :-1]
    labels = tokens[:, 1:]
    mask = batch.get("loss_mask")
    mask = mask[:, 1:] if mask is not None else jnp.ones_like(labels, jnp.float32)
    ce = cross_entropy_loss(pred, labels, mask)
    loss = ce + 0.01 * aux
    return loss, {"ce": ce, "aux": aux}


# ---------------------------------------------------------------------------
# serving: prefill + decode
# ---------------------------------------------------------------------------
def init_cache(cfg: ModelConfig, batch: int, cache_len: int, dtype=jnp.bfloat16):
    pat = _pattern(cfg)
    R, tail = cfg.pattern_repeats, cfg.tail_layers

    def one_sb(_):
        return {f"l{i}": init_block_cache(cfg, kind, batch, cache_len, dtype)
                for i, kind in enumerate(pat)}

    stacked = jax.tree.map(
        lambda *xs: jnp.stack(xs), *[one_sb(r) for r in range(R)]
    ) if R > 1 else jax.tree.map(lambda x: x[None], one_sb(0))
    tail_caches = [init_block_cache(cfg, pat[i % len(pat)], batch, cache_len, dtype)
                   for i in range(tail)]
    return {"sb": stacked, "tail": tail_caches}


def _serve_pass(params, cfg: ModelConfig, tokens, cache, cache_len, mode,
                enc_out=None, frontend_embeds=None, last_only=False):
    B, T = tokens.shape
    x = _embed(params, cfg, tokens)
    if (cfg.frontend != "none" and not _is_encdec(cfg)
            and frontend_embeds is not None):
        x = jnp.concatenate([frontend_embeds.astype(x.dtype), x], axis=1)
        T = x.shape[1]
    positions = cache_len[:, None] + jnp.arange(T)[None]
    pat = _pattern(cfg)
    x = constrain_batch(x)

    def superblock(carry, xs):
        h = carry
        sbp, sbc = xs
        new_c = {}
        for i, kind in enumerate(pat):
            h, c, _ = block_apply(sbp[f"l{i}"], cfg, kind, h, positions,
                                  cache=sbc[f"l{i}"], cache_len=cache_len,
                                  enc_out=enc_out, mode=mode)
            new_c[f"l{i}"] = c
        return constrain_batch(h), new_c

    x, new_sb = jax.lax.scan(superblock, x, (params["sb"], cache["sb"]))
    new_tail = []
    for i, bp in enumerate(params.get("tail", [])):
        x, c, _ = block_apply(bp, cfg, pat[i % len(pat)], x, positions,
                              cache=cache["tail"][i], cache_len=cache_len,
                              enc_out=enc_out, mode=mode)
        new_tail.append(c)

    if last_only:
        # prefill reads one position; a [B, T, V] logits block of a 256k
        # vocabulary would not fit beside the model
        x = x[:, -1:]
    logits = _logits(params, cfg, x)
    return logits, {"sb": new_sb, "tail": new_tail}


def decode_step(
    params,
    cfg: ModelConfig,
    tokens: jax.Array,        # i32[B, T] (T=1 for autoregressive decode)
    cache,
    cache_len: jax.Array,     # i32[B] tokens already in cache
    *,
    enc_out: Optional[jax.Array] = None,
):
    """One decode step over the stacked caches.  Returns (logits, cache')."""
    return _serve_pass(params, cfg, tokens, cache, cache_len, "decode",
                       enc_out=enc_out)


def prefill(
    params,
    cfg: ModelConfig,
    tokens: jax.Array,        # i32[B, T]
    cache,
    *,
    frontend_embeds: Optional[jax.Array] = None,
):
    """Build caches for a prompt (flash path, O(T*BS) memory).
    Returns (last_logits, cache', lengths)."""
    B = tokens.shape[0]
    enc_out = None
    fe = frontend_embeds
    if _is_encdec(cfg):
        enc_out = _run_encoder(params, cfg, frontend_embeds)
        fe = None
    zeros = jnp.zeros((B,), jnp.int32)
    logits, cache = _serve_pass(params, cfg, tokens, cache, zeros, "prefill",
                                enc_out=enc_out, frontend_embeds=fe,
                                last_only=True)
    total = tokens.shape[1] + (fe.shape[1] if fe is not None else 0)
    return logits, cache, zeros + total
