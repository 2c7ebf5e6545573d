"""Residual block assembly: norm -> mixer -> (+residual) -> norm -> ffn/moe.

One ``block_apply`` dispatches every mixer kind (attn/local/mlstm/slstm/
rglru/mamba2), handles gemma2 sandwich norms, decoder cross-attention, MoE aux
losses, and the per-kind decode caches — so the whole arch pool shares a
single scanned superblock implementation.

Local-attention decode uses a **ring cache** sized min(window, L): for
gemma2-2b at 500k context the local layers hold 4096 slots instead of 524288
— the window-expiry property the MVGC layer also exploits.
"""
from __future__ import annotations

import math
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn_mod
from repro.models.attention import KVCache, attention, init_attention
from repro.models.common import rms_norm, softcap
from repro.models.mamba2 import (
    init_mamba2, mamba2, mamba2_decode, mamba2_init_state,
)
from repro.models.mlp import init_mlp, mlp
from repro.models.moe import init_moe, moe
from repro.models.mlstm import (
    MLSTMState, init_mlstm, mlstm_chunkwise, mlstm_decode, mlstm_init_state,
    SLSTMState, init_slstm, slstm, slstm_init_state,
)
from repro.models.rglru import (
    RGLRUState, init_rglru, rglru, rglru_decode, rglru_init_state,
)

NEG_INF = -1e30


class LocalKVCache(NamedTuple):
    k: jax.Array     # [B, W, Hkv, D] ring buffer
    v: jax.Array
    pos: jax.Array   # i32[B, W] absolute position stored in each slot (-1 empty)


def _residual(cfg: ModelConfig, h: jax.Array) -> jax.Array:
    """A sublayer's output as it joins the residual stream."""
    return h * cfg.residual_mult if cfg.residual_mult != 1.0 else h


def _uses_mlp(cfg: ModelConfig, kind: str) -> bool:
    return kind in ("attn", "local", "rglru", "mamba2") and (cfg.d_ff > 0 or cfg.num_experts > 0)


def init_block(key, cfg: ModelConfig, kind: str, dtype=jnp.float32,
               cross: bool = False):
    d = cfg.d_model
    ks = jax.random.split(key, 4)
    p: dict = {"ln1": jnp.zeros((d,), dtype)}
    if kind in ("attn", "local"):
        p["mixer"] = init_attention(ks[0], cfg, dtype=dtype)
    elif kind == "mlstm":
        p["mixer"] = init_mlstm(ks[0], cfg, dtype=dtype)
    elif kind == "slstm":
        p["mixer"] = init_slstm(ks[0], cfg, dtype=dtype)
    elif kind == "rglru":
        p["mixer"] = init_rglru(ks[0], cfg, dtype=dtype)
    elif kind == "mamba2":
        p["mixer"] = init_mamba2(ks[0], cfg, dtype=dtype)
    else:
        raise ValueError(kind)
    if cfg.post_norms:
        p["ln1_post"] = jnp.zeros((d,), dtype)
    if cross:
        p["cross_ln"] = jnp.zeros((d,), dtype)
        p["cross"] = init_attention(ks[1], cfg, cross=True, dtype=dtype)
    if _uses_mlp(cfg, kind):
        p["ln2"] = jnp.zeros((d,), dtype)
        if cfg.num_experts > 0:
            p["ffn"] = init_moe(ks[2], cfg, dtype=dtype)
        else:
            p["ffn"] = init_mlp(ks[2], cfg, dtype=dtype)
        if cfg.post_norms:
            p["ln2_post"] = jnp.zeros((d,), dtype)
    return p


def init_block_cache(cfg: ModelConfig, kind: str, batch: int, cache_len: int,
                     dtype=jnp.bfloat16):
    hd, hkv = cfg.hd, cfg.num_kv_heads
    if kind == "attn":
        return KVCache(
            k=jnp.zeros((batch, cache_len, hkv, hd), dtype),
            v=jnp.zeros((batch, cache_len, hkv, hd), dtype),
        )
    if kind == "local":
        W = min(cfg.local_window or cache_len, cache_len)
        return LocalKVCache(
            k=jnp.zeros((batch, W, hkv, hd), dtype),
            v=jnp.zeros((batch, W, hkv, hd), dtype),
            pos=jnp.full((batch, W), -1, jnp.int32),
        )
    if kind == "mlstm":
        return mlstm_init_state(cfg, batch)
    if kind == "slstm":
        return slstm_init_state(cfg, batch)
    if kind == "rglru":
        return rglru_init_state(cfg, batch)
    if kind == "mamba2":
        return mamba2_init_state(cfg, batch, dtype)
    raise ValueError(kind)


def _local_ring_decode(params, cfg: ModelConfig, x, positions, cache: LocalKVCache):
    """Decode step for local attention over the ring cache."""
    B, T, d = x.shape
    q, k, v = attn_mod._project_qkv(params, cfg, x)
    if cfg.rope:
        from repro.models.common import rope
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    W = cache.k.shape[1]
    slot = positions % W                                       # [B, T]
    bidx = jnp.arange(B)[:, None] * jnp.ones((1, T), jnp.int32)
    cache = LocalKVCache(
        k=cache.k.at[bidx, slot].set(k, mode="drop"),
        v=cache.v.at[bidx, slot].set(v, mode="drop"),
        pos=cache.pos.at[bidx, slot].set(positions, mode="drop"),
    )
    D = q.shape[-1]
    scale = cfg.attn_scale or 1.0 / math.sqrt(D)
    Hkv = k.shape[2]
    qf = q.reshape(B, T, Hkv, -1, D) * jnp.asarray(scale, q.dtype)
    logits = jnp.einsum("bthgd,bshd->bthgs", qf, cache.k,
                        preferred_element_type=jnp.float32)
    if cfg.attn_softcap > 0:
        logits = softcap(logits, cfg.attn_softcap)
    cpos = cache.pos[:, None, :]                               # [B,1,W]
    rows = positions[..., None]                                # [B,T,1]
    w = cfg.local_window
    mask = (cpos >= 0) & (cpos <= rows) & (cpos > rows - w)
    logits = jnp.where(mask[:, :, None, None, :], logits, NEG_INF)
    m = logits.max(-1, keepdims=True)
    p = jnp.exp(logits - m)
    out = jnp.einsum("bthgs,bshd->bthgd",
                     (p / p.sum(-1, keepdims=True)).astype(cache.v.dtype),
                     cache.v, preferred_element_type=jnp.float32)
    out = out.reshape(B, T, cfg.num_heads, D).astype(x.dtype)
    return jnp.einsum("bthk,hkd->btd", out, params["wo"]), cache


def _prefill_local_ring(params, cfg: ModelConfig, h, positions, cache: LocalKVCache):
    """Prefill a local layer: flash-attend the prompt, keep only the last W
    tokens' K/V in the ring (earlier ones are already out of every future
    token's window)."""
    from repro.models.attention import _project_qkv, _xla_flash
    from repro.models.common import rope
    B, T, _ = h.shape
    q, k, v = _project_qkv(params, cfg, h)
    if cfg.rope:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    W = cache.k.shape[1]
    slot = jnp.where(positions >= T - W, positions % W, W)  # W = drop (dup-safe)
    bidx = jnp.arange(B)[:, None] * jnp.ones((1, T), jnp.int32)
    cache = LocalKVCache(
        k=cache.k.at[bidx, slot].set(k.astype(cache.k.dtype), mode="drop"),
        v=cache.v.at[bidx, slot].set(v.astype(cache.v.dtype), mode="drop"),
        pos=cache.pos.at[bidx, slot].set(positions, mode="drop"),
    )
    out = _xla_flash(q, k, v, causal=True, window=cfg.local_window,
                     attn_cap=cfg.attn_softcap, scale=cfg.attn_scale)
    return jnp.einsum("bthk,hkd->btd", out, params["wo"]), cache


def block_apply(
    params,
    cfg: ModelConfig,
    kind: str,
    x: jax.Array,
    positions: jax.Array,
    *,
    cache: Any = None,
    cache_len: Optional[jax.Array] = None,
    enc_out: Optional[jax.Array] = None,
    mode: str = "train",          # train | prefill | decode
    causal: bool = True,
) -> Tuple[jax.Array, Any, jax.Array]:
    """Returns (x', cache', aux_loss)."""
    assert mode in ("train", "prefill", "decode"), mode
    aux = jnp.float32(0)
    h = rms_norm(x, params["ln1"], cfg.norm_eps)
    new_cache = cache
    if kind in ("attn", "local"):
        if mode == "decode" and kind == "local":
            h, new_cache = _local_ring_decode(params["mixer"], cfg, h, positions, cache)
        elif mode == "prefill" and kind == "local":
            h, new_cache = _prefill_local_ring(params["mixer"], cfg, h, positions, cache)
        elif mode == "prefill":
            h, new_cache = attention(
                params["mixer"], cfg, h, positions, kind=kind, causal=causal,
                fill_cache=cache,
            )
        else:
            h, new_cache = attention(
                params["mixer"], cfg, h, positions, kind=kind, causal=causal,
                cache=cache if mode == "decode" else None, cache_len=cache_len,
            )
    elif kind == "mlstm":
        fn = mlstm_decode if mode == "decode" else mlstm_chunkwise
        h, new_cache = fn(params["mixer"], cfg, h, cache)
    elif kind == "slstm":
        h, new_cache = slstm(params["mixer"], cfg, h, cache)
    elif kind == "rglru":
        fn = rglru_decode if mode == "decode" else rglru
        h, new_cache = fn(params["mixer"], cfg, h, cache)
    elif kind == "mamba2":
        if mode == "decode":
            h, new_cache = mamba2_decode(params["mixer"], cfg, h, cache)
        else:
            # a prompt starts from a zero state, whatever the cache held
            h, new_cache = mamba2(params["mixer"], cfg, h, cache)
    if cfg.post_norms:
        h = rms_norm(h, params["ln1_post"], cfg.norm_eps)
    x = x + _residual(cfg, h)

    if "cross" in params:
        h = rms_norm(x, params["cross_ln"], cfg.norm_eps)
        h, _ = attention(params["cross"], cfg, h, positions, causal=False,
                         x_kv=enc_out, use_rope=False)
        x = x + h

    if "ffn" in params:
        h = rms_norm(x, params["ln2"], cfg.norm_eps)
        if cfg.num_experts > 0:
            h, aux = moe(params["ffn"], cfg, h)
        else:
            h = mlp(params["ffn"], cfg, h)
        if cfg.post_norms:
            h = rms_norm(h, params["ln2_post"], cfg.norm_eps)
        x = x + _residual(cfg, h)
    return x, new_cache, aux
