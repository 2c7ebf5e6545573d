"""jit'd public wrapper for the compact kernel with backend dispatch."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.telemetry import resolve_kernel
from repro.kernels.compact.kernel import compact_pallas, needed_pallas
from repro.kernels.compact.ref import compact_ref, needed_ref


@functools.partial(jax.jit, static_argnames=("use_kernel", "interpret", "block_s"))
def needed(
    ts: jax.Array,
    succ: jax.Array,
    ann_sorted: jax.Array,
    now: jax.Array,
    *,
    use_kernel: Optional[bool] = None,   # None: by platform (resolve_kernel)
    interpret: Optional[bool] = None,
    block_s: int = 256,
) -> jax.Array:
    """bool[S, V] needed mask; Pallas kernel on TPU, jnp reference otherwise."""
    use_kernel, interpret = resolve_kernel(use_kernel, interpret)
    if use_kernel:
        return needed_pallas(
            ts, succ, ann_sorted, now, block_s=block_s, interpret=interpret
        ).astype(jnp.bool_)
    return needed_ref(ts, succ, ann_sorted, now)


@functools.partial(jax.jit, static_argnames=("use_kernel", "interpret", "block_r"))
def compact(
    ts: jax.Array,
    succ: jax.Array,
    payload: jax.Array,
    mask: jax.Array,
    ann_sorted: jax.Array,
    now: jax.Array,
    *,
    use_kernel: Optional[bool] = None,   # None: by platform (resolve_kernel)
    interpret: Optional[bool] = None,
    block_r: int = 256,
):
    """Fused needed + splice over an [R, V] row batch.

    Returns ``(ts', succ', payload', freed, n_freed)`` — see ``compact_ref``
    for the contract.  Pallas kernel when ``use_kernel``, jnp reference
    otherwise (the two are parity-tested in tests/kernels)."""
    use_kernel, interpret = resolve_kernel(use_kernel, interpret)
    if use_kernel:
        return compact_pallas(
            ts, succ, payload, mask, ann_sorted, now,
            block_r=block_r, interpret=interpret,
        )
    return compact_ref(ts, succ, payload, mask, ann_sorted, now)
