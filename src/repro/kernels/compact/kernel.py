"""Pallas TPU kernel: the SSL compact sweep (needed(A,t) mask over slabs).

Hardware mapping (DESIGN.md §6): the paper's merge pass over (version list ×
sorted announcements) becomes a VPU broadcast-compare — the announcement
vector (P is at most a few thousand: KBs) stays resident in SMEM while the
[S, V] slab streams through in (BLOCK_S, V) tiles.  Arithmetic intensity is
O(P) per element, so for realistic P (>= 64) the sweep is compute-bound on
the VPU rather than HBM-bound — which is why fusing the mask computation into
one pass (instead of searchsorted's gather-heavy form) is the right TPU
shape.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

EMPTY = -1  # plain int: kernels must not capture traced constants
TS_MAX = 2_147_483_647
DEFAULT_BLOCK_S = 256


def _needed_tile(ts, succ, ann_ref, now):
    """needed(A, now) over one (rows, V) tile.  The announcement board sits
    in SMEM and is read one scalar per iteration, each compared against the
    whole tile: the sweep stays 2-D on the VPU for any board size P."""
    def pin(p, pinned):
        a = ann_ref[p]
        return pinned | jnp.where((ts <= a) & (a < succ), 1, 0)

    pinned = jax.lax.fori_loop(0, ann_ref.shape[0], pin,
                               jnp.zeros(ts.shape, jnp.int32))
    return (ts != EMPTY) & ((pinned != 0) | (succ > now))


def _compact_kernel(now_ref, ann_ref, ts_ref, succ_ref, out_ref):
    need = _needed_tile(ts_ref[...], succ_ref[...], ann_ref, now_ref[0])
    out_ref[...] = need.astype(jnp.int8)


def needed_pallas(
    ts: jax.Array,
    succ: jax.Array,
    ann_sorted: jax.Array,
    now: jax.Array,
    *,
    block_s: int = DEFAULT_BLOCK_S,
    interpret: bool = False,
) -> jax.Array:
    """needed(A, now) as int8[S, V] (1 = needed)."""
    S, V = ts.shape
    bs = min(block_s, S)
    now_arr = jnp.reshape(jnp.asarray(now, jnp.int32), (1,))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    tile = pl.BlockSpec((bs, V), lambda i: (i, 0))
    return pl.pallas_call(
        _compact_kernel,
        grid=(pl.cdiv(S, bs),),
        in_specs=[smem, smem, tile, tile],       # now, board; ts, succ tiles
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((S, V), jnp.int8),
        interpret=interpret,
    )(now_arr, ann_sorted, ts, succ)


def _fused_compact_kernel(
    num_rows,  # python int, closed over: guards padding rows in the count
    now_ref, ann_ref,                       # scalar-prefetched (SMEM)
    ts_ref, succ_ref, pay_ref, mask_ref,    # streamed tiles
    out_ts_ref, out_succ_ref, out_pay_ref, out_freed_ref, out_cnt_ref,
):
    ts = ts_ref[...]            # (BR, V)
    succ = succ_ref[...]        # (BR, V)
    pay = pay_ref[...]          # (BR, V)
    m = mask_ref[...]           # (BR, 1) i32: 1 = row eligible
    need = _needed_tile(ts, succ, ann_ref, now_ref[0])
    kill = (ts != EMPTY) & ~need & (m != 0)
    out_ts_ref[...] = jnp.where(kill, EMPTY, ts)
    out_succ_ref[...] = jnp.where(kill, TS_MAX, succ)
    out_pay_ref[...] = jnp.where(kill, EMPTY, pay)
    out_freed_ref[...] = jnp.where(kill, pay, EMPTY)
    # per-block freed count, broadcast over a lane-dense (8, 128) tile;
    # padding rows in the last tile must not count
    br = ts.shape[0]
    rid = jax.lax.broadcasted_iota(jnp.int32, (br, 1), 0) + pl.program_id(0) * br
    cnt = jnp.sum(jnp.where(kill & (rid < num_rows), 1, 0))
    out_cnt_ref[...] = jnp.full(out_cnt_ref.shape, cnt, jnp.int32)


def compact_pallas(
    ts: jax.Array,          # i32[R, V]
    succ: jax.Array,        # i32[R, V]
    payload: jax.Array,     # i32[R, V]
    mask: jax.Array,        # bool[R]
    ann_sorted: jax.Array,  # i32[P] (TS_MAX padded)
    now: jax.Array,         # i32[]
    *,
    block_r: int = DEFAULT_BLOCK_S,
    interpret: bool = False,
):
    """Fused needed + splice in one launch (DESIGN.md §12).

    The announcement board and the clock ride in via **scalar prefetch**
    (``PrefetchScalarGridSpec``): both live in SMEM before the first grid step
    so every (BLOCK_R, V) descriptor tile is compared against the resident
    pin vector as it streams through — no separate mask materialization, no
    second splice dispatch.  Outputs the compacted ts/succ/payload tiles, the
    freed payload handles and the exact freed count in the same pass.
    """
    R, V = ts.shape
    br = min(block_r, R)
    steps = pl.cdiv(R, br)
    now_arr = jnp.reshape(jnp.asarray(now, jnp.int32), (1,))
    mask_col = mask.astype(jnp.int32)[:, None]

    def tile(i, now_ref, ann_ref):
        return (i, 0)

    def count(i, now_ref, ann_ref):
        return (i, 0, 0)

    t_spec = pl.BlockSpec((br, V), tile)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(steps,),
        in_specs=[t_spec, t_spec, t_spec,            # ts, succ, payload
                  pl.BlockSpec((br, 1), tile)],      # row mask
        out_specs=(t_spec, t_spec, t_spec, t_spec,   # ts', succ', pay', freed
                   pl.BlockSpec((1, 8, 128), count)),  # per-block count
    )
    tile_out = jax.ShapeDtypeStruct((R, V), jnp.int32)
    new_ts, new_succ, new_pay, freed, cnt = pl.pallas_call(
        functools.partial(_fused_compact_kernel, R),
        grid_spec=grid_spec,
        out_shape=(tile_out, tile_out, tile_out, tile_out,
                   jax.ShapeDtypeStruct((steps, 8, 128), jnp.int32)),
        interpret=interpret,
    )(now_arr, ann_sorted, ts, succ, payload, mask_col)
    return new_ts, new_succ, new_pay, freed, cnt[:, 0, 0].sum()
