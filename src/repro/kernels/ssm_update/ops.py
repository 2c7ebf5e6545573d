"""jit'd public wrapper for the ssm_update kernel with backend dispatch."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.telemetry import resolve_kernel
from repro.kernels.ssm_update.kernel import ssm_update_pallas
from repro.kernels.ssm_update.ref import ssm_update_ref


@functools.partial(jax.jit, static_argnames=("use_kernel", "interpret"))
def ssm_update(
    state: jax.Array,
    x: jax.Array,
    dt: jax.Array,
    A: jax.Array,
    B: jax.Array,
    C: jax.Array,
    D: jax.Array,
    *,
    use_kernel: Optional[bool] = None,   # None: by platform (resolve_kernel)
    interpret: Optional[bool] = None,
):
    """One Mamba-2 decode step for every sequence (see ``ref.py`` for the
    layout and the recurrence).  Returns ``(y f32[Bt, H*P], state')``.
    The Pallas kernel on a TPU, which writes the new state over the old
    one's buffer; the jnp reference otherwise (parity-tested in
    tests/kernels)."""
    use_kernel, interpret = resolve_kernel(use_kernel, interpret)
    if not use_kernel:
        return ssm_update_ref(state, x, dt, A, B, C, D)
    Bt, N, HP = state.shape
    P = HP // dt.shape[1]
    f32 = jnp.float32
    lanes = lambda v: jnp.repeat(v.astype(f32), P, axis=-1)
    col = lambda m: m.astype(f32).reshape(-1, N, 1)
    y, state = ssm_update_pallas(
        state, x.astype(f32)[:, None], lanes(dt)[:, None], lanes(A)[None],
        lanes(D)[None], col(B), col(C), interpret=interpret)
    return y[:, 0], state
