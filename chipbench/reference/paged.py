"""Plain reference of the paged K/V cache's semantics, from the traffic log.

A sequence's content at any step is the K/V of the tokens it was
acknowledged for since its last acknowledged reset, in order; a snapshot
pinned after step ``p`` shows every sequence's content as of ``p``, however
the cache changes later.  The K/V of the token appended at step ``s`` to
sequence ``i`` is a function of the seed, ``s`` and ``i`` alone
(`kv_rows`), so the reference rebuilds any view's bytes without the
program: it imports nothing of it and reads none of its state.

Over several hosts, each host's pins are its own, and the collector on
every shard keeps what the global low-water mark needs: the oldest
timestamp pinned on any host that is not aged out (every host's
announcements but a stalled one's), or, with no such pin, the largest
int32, which holds nothing back.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import harness


def traffic_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size (seeds may exceed 32 bits)."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def kv_rows(key, steps, seqs, hkv: int, hd: int, dtype):
    """K and V of the token appended at ``steps[j]`` to ``seqs[j]``:
    ``[J, hkv, hd]`` each."""

    def one(s, i):
        k = jax.random.fold_in(jax.random.fold_in(key, s), i)
        kk, kv = jax.random.split(k)
        return (jax.random.normal(kk, (hkv, hd), jnp.float32).astype(dtype),
                jax.random.normal(kv, (hkv, hd), jnp.float32).astype(dtype))

    return jax.vmap(one)(steps, seqs)


_MIX = (np.uint32(2654435761), np.uint32(2246822519))
_ADD = (np.uint32(0x9E3779B9), np.uint32(0x85EBCA6B))


def digest_words(words: jax.Array) -> jax.Array:
    """Two position-weighted 32-bit sums per row of ``words [n, ...]``
    (unsigned, wrapping): u32[2, n]."""
    n = words.shape[0]
    w = words.reshape(n, -1).astype(jnp.uint32)
    idx = jnp.arange(w.shape[1], dtype=jnp.uint32)
    out = []
    for mix, add in zip(_MIX, _ADD):
        weight = (idx * mix + add) | jnp.uint32(1)
        out.append(jnp.sum((w + jnp.uint32(1)) * weight[None, :], axis=1,
                           dtype=jnp.uint32))
    return jnp.stack(out)


def _as_words(x: jax.Array) -> jax.Array:
    word = jnp.uint16 if x.dtype.itemsize == 2 else jnp.uint32
    return jax.lax.bitcast_convert_type(x, word)


def content_digest(k, v, lengths):
    """Digests of K/V content ``[n, L, hkv, hd]`` with every position at or
    past each sequence's length zeroed."""
    keep = jnp.arange(k.shape[1])[None, :] < lengths[:, None]

    def one(x):
        return digest_words(jnp.where(keep[:, :, None, None], _as_words(x),
                                      0))

    return one(k), one(v)


def view_digest(k_pages, v_pages, tables, lengths):
    """What a reader gets from a view: the K/V bytes its page tables
    expose, ``[n, MP * PS, hkv, hd]``, zero past each length, digested."""
    n, mp = tables.shape
    ps = k_pages.shape[1]

    def take(pages):
        return pages[jnp.maximum(tables, 0)].reshape(n, mp * ps,
                                                     *pages.shape[2:])

    return content_digest(take(k_pages), take(v_pages), lengths)


class TrafficLog:
    """Acknowledged appends and resets, step by step."""

    def __init__(self, num_seqs: int):
        self.n = num_seqs
        self.acked: List[np.ndarray] = []
        self.reset: List[np.ndarray] = []

    def record(self, acked: np.ndarray, reset_ok: np.ndarray) -> None:
        self.acked.append(np.asarray(acked, bool).copy())
        self.reset.append(np.asarray(reset_ok, bool).copy())

    def arrays(self):
        return np.stack(self.acked), np.stack(self.reset)


class Replay:
    """Each sequence's length and token steps after any step, from the
    log: a reset at step ``s`` empties the sequence after ``s``."""

    def __init__(self, log: TrafficLog):
        self.acked, self.reset = log.arrays()
        steps, n = self.acked.shape
        # last acknowledged reset at or before each step (-1: none)
        idx = np.where(self.reset, np.arange(steps)[:, None], -1)
        self.last_reset = np.maximum.accumulate(idx, axis=0)
        self.cum = np.cumsum(self.acked, axis=0)

    def lengths(self, step: int) -> np.ndarray:
        r = self.last_reset[step]
        before = np.where(r >= 0, self.cum[np.maximum(r, 0),
                                           np.arange(self.cum.shape[1])], 0)
        return (self.cum[step] - before).astype(np.int32)

    def token_steps(self, step: int, seq: int) -> np.ndarray:
        r = self.last_reset[step, seq]
        window = self.acked[r + 1: step + 1, seq]
        return (np.flatnonzero(window) + r + 1).astype(np.int32)


def _reference_digests(replay: Replay, step: int, seqs: np.ndarray,
                       first: int, length: int, rows_fn):
    """Digests of sequences ``seqs`` as of ``step``; their K/V is that of
    global sequences ``first + seqs``."""
    steps = np.zeros((len(seqs), length), np.int32)
    lens = np.zeros(len(seqs), np.int32)
    for j, s in enumerate(seqs):
        ts = replay.token_steps(step, int(s))[:length]
        steps[j, :len(ts)] = ts
        lens[j] = len(ts)
    who = np.broadcast_to((first + seqs.astype(np.int32))[:, None],
                          steps.shape)
    return rows_fn(jnp.asarray(steps), jnp.asarray(who), jnp.asarray(lens))


NO_PIN = 2 ** 31 - 1


def global_lwm(pins, live) -> int:
    """The mark over ``pins`` (``(host, timestamp)`` pairs) with the hosts
    whose ``live`` is false aged out."""
    held = [t for h, t in pins if live[h]]
    return min(held) if held else NO_PIN


def compare(r: harness.Run, logs: List[TrafficLog], views: List[Dict],
            audits: List[Dict], *, key, page_size: int, max_pages: int,
            hkv: int, hd: int, dtype,
            marks: Optional[List[Dict]] = None) -> None:
    """Every view and audit of the window against the reference, host by
    host (``logs[h]``; sequence ``i`` of host ``h`` carries the K/V of
    global sequence ``h * n + i``), and over several hosts each global LWM
    in ``marks`` against the pins standing when it was taken; fills
    ``r.checks``."""
    replays = [Replay(log) for log in logs]
    n = logs[0].n
    length = max_pages * page_size

    @jax.jit
    def rows_fn(steps, who, lens):
        n, L = steps.shape
        k, v = kv_rows(key, steps.reshape(-1), who.reshape(-1), hkv, hd,
                       dtype)
        k = k.reshape(n, L, hkv, hd)
        v = v.reshape(n, L, hkv, hd)
        return content_digest(k, v, lens)

    len_bad = tbl_bad = bytes_bad = 0
    at_pin: Dict = {}
    ref_cache: Dict = {}
    for vw in views:
        h = vw["host"]
        pid = (h, vw["lane"], vw["pin_step"])
        want = replays[h].lengths(vw["pin_step"])
        len_bad += int((vw["lens"] != want).sum())
        if pid not in at_pin:
            at_pin[pid] = vw
        else:
            first = at_pin[pid]
            tbl_bad += int((vw["tables"] != first["tables"]).any(axis=1).sum())
        if pid not in ref_cache:
            dk, dv = _reference_digests(replays[h], vw["pin_step"],
                                        vw["seqs"], h * n, length, rows_fn)
            ref_cache[pid] = (np.asarray(dk), np.asarray(dv))
        dk, dv = ref_cache[pid]
        bytes_bad += int(((vw["dk"] != dk).any(axis=0)
                          | (vw["dv"] != dv).any(axis=0)).sum())
    cur_bad = freed_ref = shared = 0
    for a in audits:
        want = replays[a["host"]].lengths(a["step"])
        cur_bad += int((a["lens"] != want).sum())
        freed_ref += int(a["counts"][2])
        shared += int(a["counts"][3])
    if r.traffic["pinned_lanes"]:
        r.check("no_view_read", int(not views), 0)
    r.check("no_audit", int(not audits), 0)
    r.check("view_lengths_wrong", len_bad, 0)
    r.check("view_tables_moved", tbl_bad, 0)
    r.check("view_bytes_wrong", bytes_bad, 0)
    r.check("current_lengths_wrong", cur_bad, 0)
    r.check("pages_free_but_referenced", freed_ref, 0)
    r.check("pages_shared_by_two_seqs", shared, 0)
    if marks is not None:
        r.check("no_lwm_read", int(not marks), 0)
        r.check("global_lwm_wrong",
                sum(int(m["lwm"] != global_lwm(m["pins"], m["live"]))
                    for m in marks), 0)
