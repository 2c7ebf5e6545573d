"""Mamba-2 (SSD) mixer: in-projection, causal depthwise conv with its
window state, the selective state-space recurrence, a gated RMSNorm and the
out-projection (Dao and Gu, arXiv 2405.21060; the ``granitemoehybrid``
mixer).

    [z, xBC, dt] = h W_in
    xBC = silu(conv1d(xBC) + b);   [x (H x P), B (G x N), C (G x N)] = xBC
    dt = softplus(dt + dt_bias);   A = -exp(A_log)
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_t     (per head)
    y_t = S_t C_t + D x_t
    out = rmsnorm(y * silu(z)) W_out                      (per group)

Prefill runs the chunked (state-space dual) form: a ``lax.scan`` over
chunks of ``mamba_chunk`` tokens carrying the state ``[B, H, P, N]``,
intra-chunk products in one ``[B, H, Q, Q]`` block per chunk, and leaves
the final state and the conv window in the cache.  Decode advances both by
one token, the state through the fused ``kernels/ssm_update`` step.

The cached state is ``[B, N, H*P]`` (the kernel's layout, see
``kernels/ssm_update/ref.py``) in the cache's dtype; every update is
computed in float32.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels.ssm_update import ops as ssm_ops
from repro.models.common import dense_init


class Mamba2State(NamedTuple):
    ssm: jax.Array    # [B, N, H*P] recurrent state
    conv: jax.Array   # [B, conv_width-1, conv_dim] trailing conv inputs


def init_mamba2(key, cfg: ModelConfig, dtype=jnp.float32):
    d, di, cd, H = (cfg.d_model, cfg.mamba_inner, cfg.mamba_conv_dim,
                    cfg.mamba_heads)
    ks = jax.random.split(key, 5)
    # dt in [1e-3, 1e-1] at init (softplus^-1 as the bias), A in -[1, 16]
    dt = jnp.exp(jax.random.uniform(ks[3], (H,), jnp.float32,
                                    jnp.log(1e-3), jnp.log(1e-1)))
    return {
        "in_proj": dense_init(ks[0], (d, di + cd + H), dtype=dtype),
        "conv_w": dense_init(ks[1], (cfg.conv_width, cd), dtype=dtype),
        "conv_b": jnp.zeros((cd,), dtype),
        "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
        "A_log": jnp.log(jax.random.uniform(ks[4], (H,), jnp.float32, 1, 16)
                         ).astype(dtype),
        "D": jnp.ones((H,), dtype),
        "norm": jnp.zeros((di,), dtype),
        "out_proj": dense_init(ks[2], (di, d), dtype=dtype),
    }


def mamba2_init_state(cfg: ModelConfig, batch: int,
                      dtype=jnp.bfloat16) -> Mamba2State:
    return Mamba2State(
        ssm=jnp.zeros((batch, cfg.mamba_d_state, cfg.mamba_inner), dtype),
        conv=jnp.zeros((batch, cfg.conv_width - 1, cfg.mamba_conv_dim),
                       dtype),
    )


def _in_proj(params, cfg: ModelConfig, x):
    di, cd = cfg.mamba_inner, cfg.mamba_conv_dim
    zxbcdt = jnp.einsum("btd,de->bte", x, params["in_proj"])
    return zxbcdt[..., :di], zxbcdt[..., di:di + cd], zxbcdt[..., di + cd:]


def _conv(params, cfg: ModelConfig, hist):
    """Causal depthwise conv over ``hist`` ([B, W-1+T, C], the window then
    the new inputs) and SiLU; returns ``[B, T, C]``."""
    W = cfg.conv_width
    T = hist.shape[1] - (W - 1)
    cw = params["conv_w"].astype(jnp.float32)
    out = sum(hist[:, k:k + T].astype(jnp.float32) * cw[k] for k in range(W))
    return jax.nn.silu(out + params["conv_b"].astype(jnp.float32))


def _split_xbc(cfg: ModelConfig, xbc):
    di, G, N = cfg.mamba_inner, cfg.mamba_groups, cfg.mamba_d_state
    B, T = xbc.shape[:2]
    x = xbc[..., :di].reshape(B, T, cfg.mamba_heads, cfg.mamba_head_dim)
    Bm = xbc[..., di:di + G * N].reshape(B, T, G, N)
    Cm = xbc[..., di + G * N:].reshape(B, T, G, N)
    return x, Bm, Cm


def _dt_a(params, dt):
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + params["dt_bias"].astype(jnp.float32))
    return dt, -jnp.exp(params["A_log"].astype(jnp.float32))


def _out(params, cfg: ModelConfig, y, z, dtype):
    """Gated RMSNorm over each group's channels, then the out-projection."""
    B, T = y.shape[:2]
    G = cfg.mamba_groups
    g = (y * jax.nn.silu(z.astype(jnp.float32))).reshape(B, T, G, -1)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True)
                          + cfg.norm_eps)
    g = g.reshape(B, T, -1) * (1.0 + params["norm"].astype(jnp.float32))
    return jnp.einsum("bte,ed->btd", g.astype(dtype), params["out_proj"])


def _ssd_chunked(cfg: ModelConfig, x, dt, A, Bm, Cm):
    """The SSD recurrence over a whole prompt from a zero state, chunk by
    chunk.  ``x [B, T, H, P]``, ``dt [B, T, H]`` (f32), ``Bm``/``Cm`` ``[B,
    T, G, N]``.  Returns ``y [B, T, H, P]`` (f32, without the skip) and the
    final state ``[B, H, P, N]`` (f32)."""
    Bsz, T, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    K = H // G
    Q = min(cfg.mamba_chunk, T)
    nc = -(-T // Q)
    pad = nc * Q - T
    if pad:
        # dt = 0 past the prompt: the state neither decays nor takes input
        widen = lambda a: jnp.pad(a, [(0, 0), (0, pad)]
                                  + [(0, 0)] * (a.ndim - 2))
        x, dt, Bm, Cm = widen(x), widen(dt), widen(Bm), widen(Cm)
    f32 = jnp.float32

    def chunks(a):      # [B, nc*Q, ...] -> [nc, B, Q, ...]
        return jnp.swapaxes(a.reshape(Bsz, nc, Q, *a.shape[2:]), 0, 1)

    xs = chunks(x.reshape(Bsz, -1, G, K, P).astype(f32))
    dts = chunks(dt.reshape(Bsz, -1, G, K))
    Bs, Cs = chunks(Bm.astype(f32)), chunks(Cm.astype(f32))
    causal = jnp.tril(jnp.ones((Q, Q), bool))
    Ag = A.reshape(G, K)

    def body(S, inp):   # S [B, G, K, P, N]
        xc, dtc, Bc, Cc = inp
        cs = jnp.cumsum(dtc * Ag, axis=1)                       # [B,Q,G,K]
        seg = cs[:, :, None] - cs[:, None]                      # [B,i,j,G,K]
        decay = jnp.exp(jnp.where(causal[None, :, :, None, None], seg,
                                  -jnp.inf))
        cb = jnp.einsum("bign,bjgn->bijg", Cc, Bc)              # [B,i,j,G]
        w = decay * cb[..., None] * dtc[:, None]                # [B,i,j,G,K]
        y = jnp.einsum("bijgk,bjgkp->bigkp", w, xc)
        y = y + jnp.einsum("bign,bgkpn->bigkp", Cc, S) * jnp.exp(cs)[..., None]
        to_end = jnp.exp(cs[:, -1:] - cs) * dtc                 # [B,Q,G,K]
        S = (S * jnp.exp(cs[:, -1])[..., None, None]
             + jnp.einsum("bjgk,bjgkp,bjgn->bgkpn", to_end, xc, Bc))
        return S, y

    S0 = jnp.zeros((Bsz, G, K, P, N), f32)
    S, ys = jax.lax.scan(body, S0, (xs, dts, Bs, Cs))
    y = jnp.swapaxes(ys, 0, 1).reshape(Bsz, nc * Q, H, P)[:, :T]
    return y, S.reshape(Bsz, H, P, N)


def mamba2(params, cfg: ModelConfig, x: jax.Array,
           state: Optional[Mamba2State] = None
           ) -> Tuple[jax.Array, Mamba2State]:
    """A whole prompt ``[B, T, d]`` from a zero state (training and
    prefill).  Returns the output and the state after the prompt, in the
    dtype of ``state`` (float32 when none is given)."""
    W = cfg.conv_width
    dtype = jnp.float32 if state is None else state.ssm.dtype
    z, xbc, dt = _in_proj(params, cfg, x)
    hist = jnp.pad(xbc, ((0, 0), (W - 1, 0), (0, 0)))
    xs, Bm, Cm = _split_xbc(cfg, _conv(params, cfg, hist))
    dt, A = _dt_a(params, dt)
    y, S = _ssd_chunked(cfg, xs, dt, A, Bm, Cm)
    y = y + params["D"].astype(jnp.float32)[:, None] * xs
    out = _out(params, cfg, y.reshape(*y.shape[:2], -1), z, x.dtype)
    Bsz, H, P, N = S.shape
    ssm = jnp.transpose(S, (0, 3, 1, 2)).reshape(Bsz, N, H * P)
    # the window is a slice of the whole prompt's projection: tie it to
    # the output, so it is copied out before the next layer runs and the
    # projection is not held until the layer loop writes the cache
    out, conv = jax.lax.optimization_barrier(
        (out, hist[:, -(W - 1):].astype(dtype)))
    return out, Mamba2State(ssm=ssm.astype(dtype), conv=conv)


def mamba2_decode(params, cfg: ModelConfig, x: jax.Array,
                  state: Mamba2State) -> Tuple[jax.Array, Mamba2State]:
    """One token per sequence (``x [B, 1, d]``): the conv window and the
    recurrent state each advance by one step."""
    z, xbc, dt = _in_proj(params, cfg, x)
    hist = jnp.concatenate([state.conv.astype(xbc.dtype), xbc], axis=1)
    xs, Bm, Cm = _split_xbc(cfg, _conv(params, cfg, hist))
    dt, A = _dt_a(params, dt)
    Bsz = x.shape[0]
    y, ssm = ssm_ops.ssm_update(
        state.ssm, xs.reshape(Bsz, -1), dt[:, 0], A, Bm[:, 0], Cm[:, 0],
        params["D"].astype(jnp.float32))
    out = _out(params, cfg, y[:, None], z, x.dtype)
    return out, Mamba2State(ssm=ssm,
                            conv=hist[:, 1:].astype(state.conv.dtype))
