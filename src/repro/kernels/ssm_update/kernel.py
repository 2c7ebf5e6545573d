"""Pallas TPU kernel: the fused Mamba-2 decode state update
(``mamba_ssm``'s ``selective_state_update``).

A decode step must read each sequence's recurrent state and write it back;
nothing else about the step is as large.  The kernel streams the state
through VMEM once, in ``(N, block_lanes)`` tiles of one sequence, updates
it in float32 and writes it back in its own dtype into the same buffer
(``input_output_aliases``), producing ``y`` for those channels on the way.

Layout (see ``ref.py``): the state of a sequence is ``[N, H*P]`` with the
channels on lanes, so ``x``, ``dt``, ``A``, ``D`` and ``y`` enter as
lane-dense rows expanded per channel, and ``B``/``C`` as one ``[N, 1]``
column per sequence and group.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK_LANES = 2048


def _ssm_update_kernel(s_ref, x_ref, dt_ref, a_ref, d_ref, b_ref, c_ref,
                       y_ref, o_ref):
    s = s_ref[0].astype(jnp.float32)             # (N, L)
    x = x_ref[0]                                 # (1, L)
    dt = dt_ref[0]                               # (1, L)
    s = s * jnp.exp(dt * a_ref[...]) + b_ref[0] * (dt * x)
    y_ref[0] = jnp.sum(s * c_ref[0], axis=0, keepdims=True) + d_ref[...] * x
    o_ref[0] = s.astype(o_ref.dtype)


def ssm_update_pallas(
    state: jax.Array,   # [Bt, N, HP]
    x: jax.Array,       # f32[Bt, 1, HP]
    dt: jax.Array,      # f32[Bt, 1, HP]  per channel
    a: jax.Array,       # f32[1, HP]      per channel
    d: jax.Array,       # f32[1, HP]      per channel
    b: jax.Array,       # f32[Bt*G, N, 1]
    c: jax.Array,       # f32[Bt*G, N, 1]
    *,
    block_lanes: int = DEFAULT_BLOCK_LANES,
    interpret: bool = False,
):
    """Returns ``(y f32[Bt, 1, HP], state')``; ``state`` is donated to
    ``state'`` (aliased operand 0)."""
    Bt, N, HP = state.shape
    G = b.shape[0] // Bt
    per_group = HP // G
    L = min(block_lanes, per_group)
    if per_group % L:
        raise ValueError(f"block of {L} lanes does not tile a group's "
                         f"{per_group} channels")
    blocks = per_group // L

    def tile(i, j):
        return (i, 0, j)

    def chan(i, j):
        return (0, j)

    def group(i, j):
        return (i * G + j // blocks, 0, 0)

    row = pl.BlockSpec((1, 1, L), tile)
    return pl.pallas_call(
        _ssm_update_kernel,
        grid=(Bt, HP // L),
        in_specs=[pl.BlockSpec((1, N, L), tile), row, row,
                  pl.BlockSpec((1, L), chan), pl.BlockSpec((1, L), chan),
                  pl.BlockSpec((1, N, 1), group),
                  pl.BlockSpec((1, N, 1), group)],
        out_specs=(row, pl.BlockSpec((1, N, L), tile)),
        out_shape=(jax.ShapeDtypeStruct((Bt, 1, HP), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)),
        input_output_aliases={0: 1},
        interpret=interpret,
        name="ssm_update",
    )(state, x, dt, a, d, b, c)
