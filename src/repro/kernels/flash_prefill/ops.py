"""jit'd public wrapper for flash prefill attention."""
from __future__ import annotations

import functools
from typing import Optional

import jax

from repro.core.telemetry import resolve_kernel
from repro.kernels.flash_prefill.kernel import flash_attention_pallas
from repro.kernels.flash_prefill.ref import attention_ref

STATIC = ("causal", "window", "softcap", "use_kernel", "interpret",
          "block_t", "block_s")


@functools.partial(jax.jit, static_argnames=STATIC)
def flash_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, *,
    causal: bool = True, window: int = 0, softcap: float = 0.0,
    use_kernel: bool = True, interpret: Optional[bool] = None,
    block_t: int = 128, block_s: int = 128,
) -> jax.Array:
    use_kernel, interpret = resolve_kernel(use_kernel, interpret)
    if use_kernel:
        return flash_attention_pallas(
            q, k, v, causal=causal, window=window, softcap=softcap,
            block_t=block_t, block_s=block_s, interpret=interpret)
    return attention_ref(q, k, v, causal=causal, window=window, softcap=softcap)
