"""jit'd public wrapper for version_search."""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.telemetry import resolve_kernel
from repro.kernels.version_search.kernel import search_gather_pallas, search_pallas
from repro.kernels.version_search.ref import search_gather_ref, search_ref


@functools.partial(jax.jit, static_argnames=("use_kernel", "interpret", "block_b"))
def search(
    ts: jax.Array,
    payload: jax.Array,
    slot_ids: jax.Array,
    t: jax.Array,
    *,
    use_kernel: Optional[bool] = None,   # None: by platform (resolve_kernel)
    interpret: Optional[bool] = None,
    block_b: int = 128,
) -> Tuple[jax.Array, jax.Array]:
    use_kernel, interpret = resolve_kernel(use_kernel, interpret)
    if use_kernel:
        return search_pallas(
            ts, payload, slot_ids, t, block_b=block_b, interpret=interpret
        )
    return search_ref(ts, payload, slot_ids, t)


@functools.partial(jax.jit, static_argnames=("use_kernel", "interpret", "block_b"))
def search_gather(
    ts: jax.Array,
    payload: jax.Array,
    values: jax.Array,
    slot_ids: jax.Array,
    t: jax.Array,
    *,
    use_kernel: Optional[bool] = None,   # None: by platform (resolve_kernel)
    interpret: Optional[bool] = None,
    block_b: int = 128,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Fused batched search(t) + value-row gather: one launch resolves a
    batch of (slot, ts) snapshot reads AND gathers the payload-indexed rows.
    Returns ``(rows[B, M], payload[B], found[B])``."""
    use_kernel, interpret = resolve_kernel(use_kernel, interpret)
    if use_kernel:
        return search_gather_pallas(
            ts, payload, values, slot_ids, t, block_b=block_b, interpret=interpret
        )
    return search_gather_ref(ts, payload, values, slot_ids, t)
