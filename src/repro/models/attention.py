"""Attention mixers: GQA with RoPE, sliding-window, softcap, KV cache,
cross-attention — XLA flash (scan-over-KV-blocks) for train/prefill and a
Pallas dispatch for TPU runs.

The XLA flash path is the compile-target for the dry-run: O(T * BS) live
memory instead of O(T^2), scan keeps the HLO size depth-independent, and the
online-softmax structure matches what the Pallas kernel executes on real
hardware (repro.kernels.flash_prefill — validated against the same oracle).
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import constrain_batch, dense_init, rope, softcap

NEG_INF = -1e30


def init_attention(key, cfg: ModelConfig, cross: bool = False, dtype=jnp.float32):
    d, hd, nq, nkv = cfg.d_model, cfg.hd, cfg.num_heads, cfg.num_kv_heads
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, nq, hd), dtype=dtype),
        "wk": dense_init(ks[1], (d, nkv, hd), dtype=dtype),
        "wv": dense_init(ks[2], (d, nkv, hd), dtype=dtype),
        "wo": dense_init(ks[3], (nq, hd, d), in_axis=1, dtype=dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((nq, hd), dtype)
        p["bk"] = jnp.zeros((nkv, hd), dtype)
        p["bv"] = jnp.zeros((nkv, hd), dtype)
    return p


class KVCache(NamedTuple):
    k: jax.Array        # [B, L, Hkv, D]
    v: jax.Array        # [B, L, Hkv, D]


def _project_qkv(params, cfg: ModelConfig, x, x_kv=None):
    x_kv = x if x_kv is None else x_kv
    q = jnp.einsum("btd,dhk->bthk", x, params["wq"])
    k = jnp.einsum("btd,dhk->bthk", x_kv, params["wk"])
    v = jnp.einsum("btd,dhk->bthk", x_kv, params["wv"])
    if cfg.qkv_bias:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    return q, k, v


def _xla_flash(
    q: jax.Array,  # [B, T, Hq, D]
    k: jax.Array,  # [B, S, Hkv, D]
    v: jax.Array,  # [B, S, Hkv, D]
    *,
    causal: bool,
    window: int,
    attn_cap: float,
    q_offset: jax.Array | int = 0,
    block_s: int = 512,
    scale: Optional[float] = None,
) -> jax.Array:
    """Blockwise online-softmax attention: scan over KV blocks.  ``scale``
    multiplies the logits (1/sqrt(D) when None)."""
    B, T, Hq, D = q.shape
    S = k.shape[1]
    Hkv = k.shape[2]
    G = Hq // Hkv
    scale = scale or 1.0 / math.sqrt(D)
    bs = min(block_s, S)
    n_blocks = -(-S // bs)
    pad = n_blocks * bs - S
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kb = k.reshape(B, n_blocks, bs, Hkv, D).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, n_blocks, bs, Hkv, D).transpose(1, 0, 2, 3, 4)

    qf = q.reshape(B, T, Hkv, G, D) * jnp.asarray(scale, q.dtype)
    rows = q_offset + jnp.arange(T)[:, None]  # absolute query positions

    def body(carry, blk):
        m, l, acc, sb = carry
        kblk, vblk = blk
        # bf16 operands, f32 accumulation: MXU-native; avoids materializing
        # f32 copies of Q/K (XLA otherwise hoists whole-array converts)
        logits = jnp.einsum(
            "bthgd,bshd->bthgs", qf, kblk,
            preferred_element_type=jnp.float32)
        if attn_cap > 0:
            logits = softcap(logits, attn_cap)
        cols = sb * bs + jnp.arange(bs)[None, :]
        mask = cols < S
        if causal:
            mask = mask & (cols <= rows)
        if window > 0:
            mask = mask & (cols > rows - window)
        logits = jnp.where(mask[None, :, None, None, :], logits, NEG_INF)
        m_cur = logits.max(-1)
        m_new = jnp.maximum(m, m_cur)
        p = jnp.exp(logits - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bthgs,bshd->bthgd", p.astype(v.dtype), vblk,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new, sb + 1), None

    m0 = jnp.full((B, T, Hkv, G), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, T, Hkv, G), jnp.float32)
    a0 = jnp.zeros((B, T, Hkv, G, D), jnp.float32)
    # checkpoint each KV block: backward recomputes p instead of storing the
    # [B,T,H,G,BS] residual per block — the flash-attention memory contract
    (m, l, acc, _), _ = jax.lax.scan(jax.checkpoint(body), (m0, l0, a0, 0),
                                     (kb, vb))
    l = jnp.where(l == 0.0, 1.0, l)
    out = (acc / l[..., None]).reshape(B, T, Hq, D)
    return out.astype(q.dtype)


def attention(
    params,
    cfg: ModelConfig,
    x: jax.Array,                 # [B, T, d]
    positions: jax.Array,         # i32[B, T]
    *,
    kind: str = "attn",           # attn | local
    causal: bool = True,
    cache: Optional[KVCache] = None,
    cache_len: Optional[jax.Array] = None,  # i32[B] valid tokens in cache
    x_kv: Optional[jax.Array] = None,       # cross-attention source
    use_rope: Optional[bool] = None,
    fill_cache: Optional[KVCache] = None,   # prefill: flash + write K/V here
) -> Tuple[jax.Array, Optional[KVCache]]:
    """Returns (out [B,T,d], updated cache).

    Modes:
    * train (cache None): full blockwise flash attention over x.
    * prefill (fill_cache given): flash attention over the prompt AND scatter
      its K/V into the (empty) cache — O(T * BS) memory, never O(T * L).
    * decode (cache given, T small): append K/V at cache_len, attend over the
      cache prefix.
    * cross (x_kv given): bidirectional attention over x_kv (no cache logic).
    """
    window = cfg.local_window if kind == "local" else 0
    q, k, v = _project_qkv(params, cfg, x, x_kv)
    if cfg.attn_gather_qkv:
        # column-parallel projections leave q/k/v sharded on head_dim; gather
        # them so the softmax contraction stays shard-local (sharding hd
        # through the attention core turns every QK block into a distributed
        # reduction — measured 40x collective blowup, EXPERIMENTS.md §Perf)
        q, k, v = constrain_batch(q), constrain_batch(k), constrain_batch(v)
    use_rope = cfg.rope if use_rope is None else use_rope
    if use_rope and x_kv is None:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)

    if fill_cache is not None:
        B, T = x.shape[:2]
        L = fill_cache.k.shape[1]
        idx = positions
        bidx = jnp.arange(B)[:, None] * jnp.ones((1, T), jnp.int32)
        newk = fill_cache.k.at[bidx, idx].set(k.astype(fill_cache.k.dtype),
                                              mode="drop")
        newv = fill_cache.v.at[bidx, idx].set(v.astype(fill_cache.v.dtype),
                                              mode="drop")
        out = _xla_flash(q, k, v, causal=causal, window=window,
                         attn_cap=cfg.attn_softcap, q_offset=0,
                         scale=cfg.attn_scale)
        y = jnp.einsum("bthk,hkd->btd", out, params["wo"])
        return y, KVCache(newk, newv)

    if cache is not None:
        B, T, Hkv, D = k.shape
        L = cache.k.shape[1]
        # scatter new K/V at [cache_len, cache_len+T)
        idx = cache_len[:, None] + jnp.arange(T)[None, :]        # [B, T]
        bidx = jnp.arange(B)[:, None] * jnp.ones((1, T), jnp.int32)
        newk = cache.k.at[bidx, idx].set(k, mode="drop")
        newv = cache.v.at[bidx, idx].set(v, mode="drop")
        cache = KVCache(newk, newv)
        total = cache_len + T                                    # [B]
        # attend over the cache prefix; per-batch lengths via masking.
        # bf16 operands + f32 accumulation: reading the cache in bf16 halves
        # decode HBM traffic and stops XLA hoisting f32 cache copies.
        scale = cfg.attn_scale or 1.0 / math.sqrt(D)
        qf = q.reshape(B, T, Hkv, -1, D) * jnp.asarray(scale, q.dtype)
        logits = jnp.einsum("bthgd,bshd->bthgs", qf, cache.k,
                            preferred_element_type=jnp.float32)
        if cfg.attn_softcap > 0:
            logits = softcap(logits, cfg.attn_softcap)
        cols = jnp.arange(L)[None, None, :]
        rows = positions[..., None]                              # [B, T, 1]
        mask = cols < total[:, None, None]
        if causal:
            mask = mask & (cols[0] <= rows)
        if window > 0:
            mask = mask & (cols[0] > rows - window)
        logits = jnp.where(mask[:, :, None, None, :], logits, NEG_INF)
        m = logits.max(-1, keepdims=True)
        p = jnp.exp(logits - m)
        l = p.sum(-1, keepdims=True)
        out = jnp.einsum("bthgs,bshd->bthgd", (p / l).astype(cache.v.dtype),
                         cache.v, preferred_element_type=jnp.float32)
        out = out.reshape(B, T, cfg.num_heads, D).astype(x.dtype)
    else:
        out = _xla_flash(
            q, k, v,
            causal=causal and x_kv is None,
            window=window,
            attn_cap=cfg.attn_softcap,
            q_offset=0,
            scale=cfg.attn_scale,
        )

    y = jnp.einsum("bthk,hkd->btd", out, params["wo"])
    return y, cache
