"""Pure-jnp oracle for the ssm_update kernel: one decode step of the
Mamba-2 recurrence, in the kernel's layout.

The state of one sequence is ``[N, H*P]``: the ``d_state`` axis ``N`` on
sublanes and the heads' channels on lanes, so that the per-channel input
``x`` and output ``y`` are lane-dense rows and only ``B`` and ``C`` (one
``N``-vector per group) are columns.  Head ``h`` owns lanes ``[h*P,
(h+1)*P)`` and reads group ``h // (H // G)`` of ``B`` and ``C``.

    S' = exp(dt * A) * S + dt * x (outer) B        (per head)
    y  = S' . C + D * x
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def ssm_update_ref(
    state: jax.Array,   # [Bt, N, H*P] any float dtype (bf16 in the cache)
    x: jax.Array,       # [Bt, H*P]
    dt: jax.Array,      # [Bt, H]  step sizes, softplus already applied
    A: jax.Array,       # [H]      negative decay rates
    B: jax.Array,       # [Bt, G, N]
    C: jax.Array,       # [Bt, G, N]
    D: jax.Array,       # [H]      skip
):
    """Returns ``(y [Bt, H*P] f32, state' [Bt, N, H*P])``: the update is
    computed in float32 and the new state stored in the old one's dtype."""
    Bt, N, HP = state.shape
    H, G = dt.shape[1], B.shape[1]
    P = HP // H
    f32 = jnp.float32
    lanes = lambda v: jnp.repeat(v.astype(f32), P, axis=-1)   # per head -> lanes
    cols = lambda m: jnp.repeat(m.astype(f32), HP // G, axis=1)  # [Bt,HP,N]
    dtl = lanes(dt)                                           # [Bt, HP]
    xf = x.astype(f32)
    s = (state.astype(f32) * jnp.exp(dtl * lanes(A))[:, None, :]
         + jnp.swapaxes(cols(B), 1, 2) * (dtl * xf)[:, None, :])
    y = (jnp.sum(s * jnp.swapaxes(cols(C), 1, 2), axis=1)
         + lanes(D)[None] * xf)
    return y, s.astype(state.dtype)
