"""Pallas TPU kernel: batched version search (the paper's ``search(t)``).

The list traversal becomes a slab-row gather + masked max.  The queried rows
are gathered by XLA before the launch (``ts[slot_ids]``); one grid step then
resolves a (BLOCK_B, V) tile of queries.  V is the slab width (small, e.g.
8-32), so the reduction is a cheap VPU max-scan across lanes.

Per-query vectors travel as ``[B, 1]`` columns: a rank-1 block would have to
be a multiple of 128 (512 for int8) or the whole array, and a column keeps
the per-query values on sublanes, where they broadcast against the tile.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

EMPTY = -1                      # plain ints: no captured tracers in kernels
NEG_INF_I32 = -2_147_483_648
DEFAULT_BLOCK_B = 128


def _resolve(t, rows_ts, rows_pay):
    """search(t) over one tile: ``(payload[BB, 1], found[BB, 1])``, taking
    the first column of the newest version at or before ``t`` (argmax's
    tie rule, so the kernel matches ``ref.search_ref`` bit for bit)."""
    V = rows_ts.shape[1]
    ok = (rows_ts != EMPTY) & (rows_ts <= t)
    masked = jnp.where(ok, rows_ts, NEG_INF_I32)
    best = masked.max(axis=1, keepdims=True)
    col = jax.lax.broadcasted_iota(jnp.int32, rows_ts.shape, 1)
    first = jnp.where(masked == best, col, V).min(axis=1, keepdims=True)
    found = jnp.where(ok, 1, 0).max(axis=1, keepdims=True) > 0
    pay = jnp.where(col == first, rows_pay, 0).sum(axis=1, keepdims=True)
    return jnp.where(found, pay, EMPTY), found


def _search_kernel(t_ref, ts_ref, pay_ref, out_pay_ref, out_found_ref):
    pay, found = _resolve(t_ref[...], ts_ref[...], pay_ref[...])
    out_pay_ref[...] = pay
    out_found_ref[...] = found.astype(jnp.int32)


def search_pallas(
    ts: jax.Array,        # i32[S, V]
    payload: jax.Array,   # i32[S, V]
    slot_ids: jax.Array,  # i32[B]
    t: jax.Array,         # i32[B]
    *,
    block_b: int = DEFAULT_BLOCK_B,
    interpret: bool = False,
):
    S, V = ts.shape
    B = slot_ids.shape[0]
    bb = min(block_b, B)
    rows_ts = ts[slot_ids]          # [B, V]
    rows_pay = payload[slot_ids]    # [B, V]
    col = pl.BlockSpec((bb, 1), lambda i: (i, 0))
    tile = pl.BlockSpec((bb, V), lambda i: (i, 0))
    pay, found = pl.pallas_call(
        _search_kernel,
        grid=(pl.cdiv(B, bb),),
        in_specs=[col, tile, tile],
        out_specs=(col, col),
        out_shape=(jax.ShapeDtypeStruct((B, 1), jnp.int32),
                   jax.ShapeDtypeStruct((B, 1), jnp.int32)),
        interpret=interpret,
    )(t[:, None], rows_ts, rows_pay)
    return pay[:, 0], found[:, 0] != 0


def _search_gather_kernel(
    t_ref, ts_ref, pay_ref, val_ref,
    out_rows_ref, out_pay_ref, out_found_ref,
):
    pay, found = _resolve(t_ref[...], ts_ref[...], pay_ref[...])
    out_pay_ref[...] = pay
    out_found_ref[...] = found.astype(jnp.int32)
    # gather the resolved value rows from the VMEM-resident values block:
    # per query, reduce its row index to a scalar and copy one row by ref
    # indexing (EMPTY-filled when the query found nothing)
    T = val_ref.shape[0]
    key = jnp.where(found, jnp.clip(pay, 0, T - 1), EMPTY)     # (BB, 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, key.shape, 0)

    def body(i, carry):
        k = jnp.sum(jnp.where(lane == i, key, 0))
        row = val_ref[pl.ds(jnp.maximum(k, 0), 1), :]           # (1, M)
        out_rows_ref[pl.ds(i, 1), :] = jnp.where(k >= 0, row, EMPTY)
        return carry

    jax.lax.fori_loop(0, key.shape[0], body, 0)


def search_gather_pallas(
    ts: jax.Array,        # i32[S, V]
    payload: jax.Array,   # i32[S, V]
    values: jax.Array,    # i32[T, M]
    slot_ids: jax.Array,  # i32[B]
    t: jax.Array,         # i32[B]
    *,
    block_b: int = DEFAULT_BLOCK_B,
    interpret: bool = False,
):
    """One launch: batched search(t) + gather of the resolved value rows.

    ``values`` is held whole in VMEM (padded to 128 lanes, double-buffered),
    so ``T * 128 * 4 * 2`` bytes must fit the kernel's VMEM budget: 8 MiB
    for the 8192 page-table versions of a 1024-sequence paged cache."""
    S, V = ts.shape
    T, M = values.shape
    B = slot_ids.shape[0]
    bb = min(block_b, B)
    rows_ts = ts[slot_ids]          # [B, V] (pre-gathered; see search_pallas)
    rows_pay = payload[slot_ids]    # [B, V]
    col = pl.BlockSpec((bb, 1), lambda i: (i, 0))
    tile = pl.BlockSpec((bb, V), lambda i: (i, 0))
    rows, pay, found = pl.pallas_call(
        _search_gather_kernel,
        grid=(pl.cdiv(B, bb),),
        in_specs=[col, tile, tile,
                  pl.BlockSpec((T, M), lambda i: (0, 0))],  # values (resident)
        out_specs=(pl.BlockSpec((bb, M), lambda i: (i, 0)), col, col),
        out_shape=(
            jax.ShapeDtypeStruct((B, M), jnp.int32),
            jax.ShapeDtypeStruct((B, 1), jnp.int32),
            jax.ShapeDtypeStruct((B, 1), jnp.int32),
        ),
        interpret=interpret,
    )(t[:, None], rows_ts, rows_pay, values)
    return rows, pay[:, 0], found[:, 0] != 0
