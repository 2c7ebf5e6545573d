"""The benchmark's weights for a dense GQA decoder, and a plain float32
forward pass over them.

`make_weights` draws every weight from the seed, on the device in one
jitted call, in the layout the serving system takes (its parameter tree).
The reference `forward` regenerates the same weights itself and follows the
published architecture in straightforward ``jax.numpy``: token embedding;
per layer RMSNorm, grouped-query attention with rotary positions and a
causal mask, residual, RMSNorm, SwiGLU MLP, residual; a final RMSNorm and
the output head.  It imports nothing of the system and takes nothing that
the system made.

`forward` with ``quant="fp8"`` is the control: every weight matrix rounded
to float8 (e4m3, one scale per output channel), the next precision below
the bfloat16 the configuration serves in.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from chipbench.reference.paged import traffic_key


def shapes(cfg: Dict) -> Dict:
    """The parameter tree's leaves as ``(shape, scale)`` pairs; a scale of
    ``None`` marks a norm weight (stored as ``1 + w``)."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, L, v = cfg["head_dim"], cfg["num_hidden_layers"], cfg["vocab_size"]
    tree = {
        "embed": ((v, d), 0.02),
        "final_norm": ((d,), None),
        "sb": {"l0": {
            "ln1": ((L, d), None),
            "ln2": ((L, d), None),
            "mixer": {"wq": ((L, d, nq, hd), d ** -0.5),
                      "wk": ((L, d, nkv, hd), d ** -0.5),
                      "wv": ((L, d, nkv, hd), d ** -0.5),
                      "wo": ((L, nq, hd, d), (nq * hd) ** -0.5)},
            "ffn": {"wg": ((L, d, f), d ** -0.5),
                    "wu": ((L, d, f), d ** -0.5),
                    "wd": ((L, f, d), f ** -0.5)},
        }},
    }
    if not cfg.get("tie_word_embeddings", True):
        tree["unembed"] = ((v, d), 0.02)
    return tree


def _leaves(tree, prefix=()):
    for k in sorted(tree):
        v = tree[k]
        if isinstance(v, dict):
            yield from _leaves(v, prefix + (k,))
        else:
            yield prefix + (k,), v


@functools.partial(jax.jit, static_argnums=(0, 2))
def _draw(spec, key, dtype):
    out = []
    for i, (shape, scale) in enumerate(spec):
        x = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        x = x * (0.1 if scale is None else scale)
        out.append(x.astype(jnp.bfloat16).astype(dtype))
    return out


def make_weights(cfg: Dict, seed: int, dtype=jnp.bfloat16):
    """Every weight from ``seed``, rounded to bfloat16 (the served type) and
    held as ``dtype``: the system gets them in bfloat16, the reference the
    same values in float32."""
    items = list(_leaves(shapes(cfg)))
    arrays = _draw(tuple(s for _, s in items), traffic_key(seed),
                   jnp.dtype(dtype))
    tree: Dict = {}
    for (path, _), a in zip(items, arrays):
        node = tree
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = a
    return tree


# ---------------------------------------------------------------------------
# the reference forward pass
# ---------------------------------------------------------------------------
def _rms(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * (1.0 + w)


def _rope(x, pos, theta):
    """Rotary positions on interleaved pairs ``(x[2i], x[2i+1])``."""
    hd = x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd // 2, dtype=jnp.float32) / (hd // 2))
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]      # [T, hd/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.reshape(x.shape)


def _fp8(w, axis):
    """Round ``w`` to float8 e4m3 with one scale per output channel (every
    axis but ``axis``, the contracted one)."""
    amax = jnp.max(jnp.abs(w), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    q = (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return q * scale


def _layer(x, p, cfg, pos, quant):
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    nq, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    W = (lambda w, axis: _fp8(w, axis)) if quant == "fp8" else \
        (lambda w, axis: w)
    T = x.shape[0]
    h = _rms(x, p["ln1"], eps)
    q = jnp.einsum("td,dhk->thk", h, W(p["mixer"]["wq"], 0))
    k = jnp.einsum("td,dhk->thk", h, W(p["mixer"]["wk"], 0))
    v = jnp.einsum("td,dhk->thk", h, W(p["mixer"]["wv"], 0))
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    g = nq // nkv
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    s = jnp.einsum("thk,shk->hts", q, k) / jnp.sqrt(jnp.float32(hd))
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    s = jnp.where(causal[None], s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hts,shk->thk", a, v)
    x = x + jnp.einsum("thk,hkd->td", o, W(p["mixer"]["wo"], (0, 1)))
    h = _rms(x, p["ln2"], eps)
    gate = jax.nn.silu(jnp.einsum("td,df->tf", h, W(p["ffn"]["wg"], 0)))
    up = jnp.einsum("td,df->tf", h, W(p["ffn"]["wu"], 0))
    return x + jnp.einsum("tf,fd->td", gate * up, W(p["ffn"]["wd"], 0))


def forward(weights, cfg: Dict, tokens: jax.Array, last: int,
            quant: Optional[str] = None) -> jax.Array:
    """float32 logits ``[last, V]`` at the last ``last`` positions of one
    sequence ``tokens [T]``, computed layer by layer."""
    with jax.default_matmul_precision("highest"):
        return _forward(weights, cfg_key(cfg), tokens, last, quant)


def cfg_key(cfg: Dict):
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "rms_norm_eps", "rope_theta", "num_hidden_layers")
    return tuple((k, cfg[k]) for k in keys)


@functools.partial(jax.jit, static_argnums=(1, 3, 4))
def _forward(weights, ckey, tokens, last, quant):
    cfg = dict(ckey)
    emb = weights["embed"]
    head = weights.get("unembed", emb)
    if quant == "fp8":
        emb, head = _fp8(emb, 1), _fp8(head, 1)
    x = emb[tokens]
    pos = jnp.arange(tokens.shape[0])
    layers = weights["sb"]["l0"]

    def body(x, p):
        return _layer(x, p, cfg, pos, quant), None

    x, _ = jax.lax.scan(body, x, layers)
    x = _rms(x[-last:], weights["final_norm"], cfg["rms_norm_eps"])
    return jnp.einsum("td,vd->tv", x, head)
