"""The benchmark's weights for a Mamba-2/attention hybrid
(``granitemoehybrid``), and a plain float32 forward pass over them.

Every layer's weights are drawn from the seed with keys of their own (the
seed's key folded with the layer's index, then with the leaf's), so one
layer can be drawn without the others.  `make_weights` draws them all in
bfloat16 (the served type) and stacks them in the serving system's
parameter tree.  The reference `forward` draws each layer again, in
float32, inside its pass, one layer at a time: float32 weights of every
layer together would not fit beside the rest on one chip.  It follows the
published architecture in straightforward ``jax.numpy``:

- embeddings times ``embedding_multiplier``;
- per layer ``h = x + residual_multiplier * mixer(rmsnorm(x))`` and
  ``x' = h + residual_multiplier * swiglu(rmsnorm(h))``;
- the Mamba-2 mixer: in-projection to ``[z, xBC, dt]``; a causal depthwise
  conv with bias over xBC, then SiLU; ``dt = softplus(dt + dt_bias)``,
  ``A = -exp(A_log)``; the recurrence ``S_t = exp(dt A) S_{t-1} + dt x_t
  (outer) B_t``, ``y = S_t C_t + D x_t``, run token by token (a
  ``lax.scan`` over time, not the chunked form the system runs); then
  ``rmsnorm(y * silu(z))`` and the out-projection;
- attention without positions (NoPE), grouped K/V heads, a causal mask
  and the logit scale ``attention_multiplier``;
- a final RMSNorm, the tied head, and logits over ``logits_scaling``.

It imports nothing of the system and takes nothing the system made.  With
``quant="fp8"`` (the control) every weight matrix is rounded to float8
(e4m3, one scale per output channel), the precision below the bfloat16 the
configuration serves in.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp

from chipbench.reference.model import _fp8, _rms
from chipbench.reference.paged import traffic_key


def layer_kinds(cfg: Dict) -> List[str]:
    """``mamba`` or ``attention`` for each layer, as the source lists them."""
    return list(cfg["layer_types"])


def period(cfg: Dict) -> int:
    """The length of the shortest pattern whose repeats give every layer."""
    kinds = layer_kinds(cfg)
    L = len(kinds)
    return next(p for p in range(1, L + 1)
                if L % p == 0 and kinds == kinds[:p] * (L // p))


def dims(cfg: Dict) -> Dict[str, int]:
    """The sizes the layers use, under short names: Mamba heads ``H`` of
    ``P`` channels, ``G`` groups of ``N`` state, ``di`` inner and ``cd``
    conv channels, conv width ``W``, MLP width ``f``, ``nq`` and ``nkv``
    attention heads of ``hd``."""
    d = cfg["hidden_size"]
    H, P = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    G, N = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    di = cfg["mamba_expand"] * d
    if di != H * P:
        raise ValueError(f"mamba_expand * hidden_size = {di} is not "
                         f"mamba_n_heads * mamba_d_head = {H * P}")
    return {"d": d, "H": H, "P": P, "G": G, "N": N, "di": di,
            "cd": di + 2 * G * N, "W": cfg["mamba_d_conv"],
            "f": cfg["shared_intermediate_size"],
            "nq": cfg["num_attention_heads"],
            "nkv": cfg["num_key_value_heads"],
            "hd": d // cfg["num_attention_heads"]}


def layer_shapes(cfg: Dict, kind: str) -> Dict:
    """One layer's leaves as ``(shape, scale, offset)``: a leaf is ``offset
    + scale * normal``.  Norm weights are stored as ``w`` and applied as
    ``1 + w``."""
    m = dims(cfg)
    d, f, H = m["d"], m["f"], m["H"]
    tree: Dict = {
        "ln1": ((d,), 0.1, 0.0),
        "ln2": ((d,), 0.1, 0.0),
        "ffn": {"wg": ((d, f), d ** -0.5, 0.0),
                "wu": ((d, f), d ** -0.5, 0.0),
                "wd": ((f, d), f ** -0.5, 0.0)},
    }
    if kind == "mamba":
        # decay rates exp(A_log) around 4 and step sizes softplus(. - 4)
        # around 0.02-0.1: a state that remembers tens of tokens
        tree["mixer"] = {
            "in_proj": ((d, m["di"] + m["cd"] + H), d ** -0.5, 0.0),
            "conv_w": ((m["W"], m["cd"]), m["W"] ** -0.5, 0.0),
            "conv_b": ((m["cd"],), 0.1, 0.0),
            "dt_bias": ((H,), 1.0, -4.0),
            "A_log": ((H,), 0.5, 1.4),
            "D": ((H,), 0.1, 1.0),
            "norm": ((m["di"],), 0.1, 0.0),
            "out_proj": ((m["di"], d), m["di"] ** -0.5, 0.0),
        }
    elif kind == "attention":
        nq, nkv, hd = m["nq"], m["nkv"], m["hd"]
        tree["mixer"] = {"wq": ((d, nq, hd), d ** -0.5, 0.0),
                         "wk": ((d, nkv, hd), d ** -0.5, 0.0),
                         "wv": ((d, nkv, hd), d ** -0.5, 0.0),
                         "wo": ((nq, hd, d), (nq * hd) ** -0.5, 0.0)}
    else:
        raise ValueError(f"no reference for layer type {kind!r}")
    return tree


def top_shapes(cfg: Dict) -> Dict:
    """The embedding, also the head, and the final norm.  The embedding's
    scale is ``0.06 / embedding_multiplier``, so tokens enter the residual
    stream at 0.06 per channel: at a scale of 0.02 the tied head would
    give each position's own token the top logit, and every pick would
    repeat its input whatever the layers computed."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    if not cfg.get("tie_word_embeddings", True):
        raise ValueError("the reference ties the head to the embedding")
    return {"embed": ((v, d), 0.06 / cfg["embedding_multiplier"], 0.0),
            "final_norm": ((d,), 0.1, 0.0)}


def _leaves(tree, prefix=()):
    for k in sorted(tree):
        v = tree[k]
        if isinstance(v, dict):
            yield from _leaves(v, prefix + (k,))
        else:
            yield prefix + (k,), v


def _nest(paths, arrays) -> Dict:
    tree: Dict = {}
    for path, a in zip(paths, arrays):
        node = tree
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = a
    return tree


@functools.partial(jax.jit, static_argnums=(0, 2))
def _draw(spec, keys, dtype):
    """Each leaf of ``spec`` for each key in ``keys`` (one per layer),
    stacked on a leading axis; rounded to bfloat16, held as ``dtype``."""
    def one(key):
        out = []
        for i, (shape, scale, offset) in enumerate(spec):
            x = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            out.append((offset + scale * x).astype(jnp.bfloat16)
                       .astype(dtype))
        return out
    return jax.vmap(one)(keys)


def _keys(seed: int, layers) -> jax.Array:
    base = traffic_key(seed)
    return jnp.stack([jax.random.fold_in(base, int(i)) for i in layers])


def draw_layer(cfg: Dict, seed: int, layer: int, dtype=jnp.float32):
    """Layer ``layer``'s weights (``layer == num_hidden_layers``: the
    embedding and the final norm)."""
    tree = (top_shapes(cfg) if layer == len(layer_kinds(cfg))
            else layer_shapes(cfg, layer_kinds(cfg)[layer]))
    items = list(_leaves(tree))
    arrays = _draw(tuple(s for _, s in items), _keys(seed, [layer]),
                   jnp.dtype(dtype))
    return _nest([p for p, _ in items], [a[0] for a in arrays])


def make_weights(cfg: Dict, seed: int, dtype=jnp.bfloat16) -> Dict:
    """Every weight from ``seed`` in the system's parameter tree: the
    embedding and final norm, and ``sb.l<i>``, the ``i``-th layer of each
    period, stacked over the periods."""
    kinds, p = layer_kinds(cfg), period(cfg)
    L = len(kinds)
    params = draw_layer(cfg, seed, L, dtype)
    params["sb"] = {}
    for i in range(p):
        items = list(_leaves(layer_shapes(cfg, kinds[i])))
        arrays = _draw(tuple(s for _, s in items),
                       _keys(seed, range(i, L, p)), jnp.dtype(dtype))
        params["sb"][f"l{i}"] = _nest([q for q, _ in items], arrays)
    return params


# ---------------------------------------------------------------------------
# the reference forward pass
# ---------------------------------------------------------------------------
def _mamba(x, p, cfg, W):
    """The Mamba-2 mixer over ``x [S, T, d]``, token by token.  Returns the
    output and the state after the last token, ``[S, H, P, N]``."""
    m = dims(cfg)
    H, P, G, N, di, cd = m["H"], m["P"], m["G"], m["N"], m["di"], m["cd"]
    S, T, _ = x.shape
    zxbcdt = jnp.einsum("std,de->ste", x, W(p["in_proj"], 0))
    z, xbc, dt = (zxbcdt[..., :di], zxbcdt[..., di:di + cd],
                  zxbcdt[..., di + cd:])
    K = m["W"]
    hist = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    conv = sum(hist[:, k:k + T] * p["conv_w"][k] for k in range(K))
    xbc = jax.nn.silu(conv + p["conv_b"])
    xs = xbc[..., :di].reshape(S, T, H, P)
    Bm = xbc[..., di:di + G * N].reshape(S, T, G, N)
    Cm = xbc[..., di + G * N:].reshape(S, T, G, N)
    # head h reads group h // (H // G)
    Bm, Cm = jnp.repeat(Bm, H // G, axis=2), jnp.repeat(Cm, H // G, axis=2)
    dt = jax.nn.softplus(dt + p["dt_bias"])                   # [S, T, H]
    A = -jnp.exp(p["A_log"])

    def step(state, inp):                                     # [S,H,P,N]
        x_t, dt_t, B_t, C_t = inp
        state = (jnp.exp(dt_t * A)[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None] * B_t[:, :, None, :])
        y = jnp.einsum("shpn,shn->shp", state, C_t) + p["D"][:, None] * x_t
        return state, y

    time = lambda a: jnp.swapaxes(a, 0, 1)
    state, y = jax.lax.scan(step, jnp.zeros((S, H, P, N), jnp.float32),
                            (time(xs), time(dt), time(Bm), time(Cm)))
    g = (time(y).reshape(S, T, di) * jax.nn.silu(z)).reshape(S, T, G, -1)
    g = g / jnp.sqrt(jnp.mean(g * g, axis=-1, keepdims=True)
                     + cfg["rms_norm_eps"])
    g = g.reshape(S, T, di) * (1.0 + p["norm"])
    return jnp.einsum("ste,ed->std", g, W(p["out_proj"], 0)), state


def _attention(x, p, cfg, W):
    m = dims(cfg)
    nq, nkv = m["nq"], m["nkv"]
    T = x.shape[1]
    q = jnp.einsum("std,dhk->sthk", x, W(p["wq"], 0))
    k = jnp.einsum("std,dhk->sthk", x, W(p["wk"], 0))
    v = jnp.einsum("std,dhk->sthk", x, W(p["wv"], 0))
    k = jnp.repeat(k, nq // nkv, axis=2)
    v = jnp.repeat(v, nq // nkv, axis=2)
    s = jnp.einsum("sthk,suhk->shtu", q, k) * cfg["attention_multiplier"]
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    a = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("shtu,suhk->sthk", a, v)
    return jnp.einsum("sthk,hkd->std", o, W(p["wo"], (0, 1)))


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _layer(x, p, ckey, kind, quant):
    cfg = dict(ckey)
    W = (lambda w, axis: _fp8(w, axis)) if quant == "fp8" else \
        (lambda w, axis: w)
    eps, r = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    h = _rms(x, p["ln1"], eps)
    if kind == "mamba":
        h, _ = _mamba(h, p["mixer"], cfg, W)
    else:
        h = _attention(h, p["mixer"], cfg, W)
    x = x + r * h
    h = _rms(x, p["ln2"], eps)
    gate = jax.nn.silu(jnp.einsum("std,df->stf", h, W(p["ffn"]["wg"], 0)))
    up = jnp.einsum("std,df->stf", h, W(p["ffn"]["wu"], 0))
    return x + r * jnp.einsum("stf,fd->std", gate * up, W(p["ffn"]["wd"], 0))


@functools.partial(jax.jit, static_argnums=(2, 3))
def _embed(tokens, embed, ckey, quant):
    cfg = dict(ckey)
    emb = _fp8(embed, 1) if quant == "fp8" else embed
    return emb[tokens] * cfg["embedding_multiplier"]


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _head(x, embed, final_norm, ckey, quant, last):
    cfg = dict(ckey)
    head = _fp8(embed, 1) if quant == "fp8" else embed
    x = _rms(x[:, -last:], final_norm, cfg["rms_norm_eps"])
    return jnp.einsum("std,vd->stv", x, head) / cfg["logits_scaling"]


def cfg_key(cfg: Dict):
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "rms_norm_eps", "mamba_n_heads", "mamba_d_head", "mamba_n_groups",
            "mamba_d_state", "mamba_expand", "mamba_d_conv",
            "shared_intermediate_size", "attention_multiplier",
            "embedding_multiplier", "residual_multiplier", "logits_scaling")
    return tuple((k, cfg[k]) for k in keys)


def forward(cfg: Dict, seed: int, tokens: jax.Array, last: int,
            quant: Optional[str] = None) -> jax.Array:
    """float32 logits ``[S, last, V]`` at the last ``last`` positions of the
    sequences ``tokens [S, T]``, layer by layer, each layer's weights drawn
    from ``seed`` as it is reached."""
    ckey, kinds = cfg_key(cfg), layer_kinds(cfg)
    with jax.default_matmul_precision("highest"):
        top = draw_layer(cfg, seed, len(kinds))
        x = _embed(tokens, top["embed"], ckey, quant)
        for i, kind in enumerate(kinds):
            x = _layer(x, draw_layer(cfg, seed, i), ckey, kind, quant)
        return _head(x, top["embed"], top["final_norm"], ckey, quant, last)
