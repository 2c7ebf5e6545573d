"""jit'd public wrapper for paged decode attention."""
from __future__ import annotations

import functools
from typing import Optional

import jax

from repro.core.telemetry import resolve_kernel
from repro.kernels.decode_attention.kernel import paged_decode_pallas
from repro.kernels.decode_attention.ref import paged_decode_ref


@functools.partial(jax.jit, static_argnames=("use_kernel", "interpret"))
def paged_decode(
    q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
    page_table: jax.Array, lengths: jax.Array, *,
    use_kernel: bool = True, interpret: Optional[bool] = None,
) -> jax.Array:
    use_kernel, interpret = resolve_kernel(use_kernel, interpret)
    if use_kernel:
        return paged_decode_pallas(
            q, k_pages, v_pages, page_table, lengths, interpret=interpret)
    return paged_decode_ref(q, k_pages, v_pages, page_table, lengths)
