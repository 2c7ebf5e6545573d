"""The paged K/V cache under request traffic with pinned snapshot readers,
on one host or sharded over several.

One general generator for every paged mix: the mix's data file gives the
sequence count, the range of request lengths, the GC policy and slab
depth, the reader lanes and how often they re-pin and re-read, and how
often the page accounting is audited; a mix with ``hosts`` above 1 runs
``ShardedPagedKVEngine``, one shard per chip, and may stall one host
(``stall_host`` after step ``stall_at``) so that it is aged out of the
global low-water mark.  The configuration gives the K/V geometry and the
pool of each host: ``kv_pool_pages`` pages of ``kv_page_tokens`` tokens,
each holding the K/V of ``kv_pool_layers`` layers.

Each sequence serves one request at a time: it appends one token per step
until it reaches its target length, is reset (a new empty page table), and
takes the next target.  Every seed serves one fixed schedule of lengths,
spread evenly over the mix's range; the seed deals its rows out to the
sequences and draws the K/V (`Lengths`), so every seed does the same work
in another order; every host runs the same schedule with K/V of its own.  Every step appends for all sequences of all live
hosts at once (``step``, which reclaims and retries under pressure) and
resets the finished ones.  Reader lanes stay pinned throughout: lane ``L``
of host ``h`` re-pins every ``repin_every`` steps, at an offset staggered
over lanes and hosts, reads the whole snapshot (page tables and lengths of
every sequence, through ``view_at``) and gathers the K/V of its
``seqs_per_pin`` longest sequences; every ``reread_every`` steps each
pinned lane reads and gathers again.  A stalled host keeps its pins and
goes on reading them.

Before the window the same traffic runs ``warm_steps`` steps, so the pool
holds a mix of lengths (and garbage) when measuring starts.

What is compared for ``correct``, after the window, against a plain
reference built from the traffic log alone (`chipbench.reference.paged`):
every view's lengths at its pin, its tables and lengths at each re-read,
and the K/V bytes it exposes; at every audit, the current lengths of every
sequence, pages free while a live view references them, and pages
referenced by two sequences; on several hosts, also the global low-water
mark that the step's GC used, against the oldest pin of the hosts not aged
out.
"""
from __future__ import annotations

import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import harness
from chipbench.reference import paged as ref


class Lengths:
    """Request lengths from one fixed schedule: ``pool`` targets spread
    evenly over ``[lo, hi]`` in one fixed order, dealt round-robin into one
    row of targets per sequence slot.  Each slot's first request is cut to
    an evenly spread share of its target, as if the traffic had been
    running, so the slots finish at staggered steps from the start.  The
    seed only deals the slots out to the sequences: every seed appends,
    resets and finishes the same requests at the same steps."""

    def __init__(self, num_seqs: int, lo: int, hi: int, seed: int,
                 pool: int = 4096):
        fixed = np.random.default_rng(0)
        base = lo + (np.arange(pool, dtype=np.int64) * (hi - lo + 1)) // pool
        queue = fixed.permutation(base)[:pool - pool % num_seqs]
        share = (fixed.permutation(num_seqs) + 1) / num_seqs
        deal = np.random.default_rng(seed).permutation(num_seqs)
        # sequence i serves slot deal[i]: targets queue[deal[i]::num_seqs]
        self.sched = queue.reshape(-1, num_seqs).T[deal]
        self.turn = np.zeros(num_seqs, np.int64)
        self.length = np.zeros(num_seqs, np.int64)
        self.target = np.maximum(1, np.ceil(self.sched[:, 0] * share[deal])
                                 ).astype(np.int64)

    def advance(self, acked: np.ndarray) -> np.ndarray:
        """Count one token per acknowledged append; returns the sequences
        that reached their target."""
        self.length += acked
        return self.length >= self.target

    def restart(self, ok: np.ndarray) -> None:
        """Sequences whose reset was acknowledged take their next target."""
        self.length[ok] = 0
        self.turn[ok] += 1
        self.target[ok] = self.sched[ok, self.turn[ok] % self.sched.shape[1]]


def _audit_fn(num_pages: int, page_size: int):
    """Page accounting over the live views: the current tables and every
    pinned lane's.  Returns i32[4]: pages held (not free), pages needed
    (referenced within some live view's length), needed pages that are free,
    needed pages that two sequences reference."""

    def audit(free, tables, lengths):
        # tables: [V, n, MP] views (current first), lengths: [V, n]
        v, n, mp = tables.shape
        used = (jnp.arange(mp)[None, None, :] * page_size
                < lengths[:, :, None])
        page = jnp.where(used & (tables >= 0), tables, num_pages)
        seq = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[None, :, None],
                               page.shape)
        flat, sflat = page.reshape(-1), seq.reshape(-1)
        needed = jnp.zeros((num_pages,), bool).at[flat].set(True, mode="drop")
        lo = jnp.full((num_pages,), n, jnp.int32).at[flat].min(sflat,
                                                               mode="drop")
        hi = jnp.full((num_pages,), -1, jnp.int32).at[flat].max(sflat,
                                                                mode="drop")
        return jnp.stack([
            num_pages - free.sum(),
            needed.sum(),
            (needed & free).sum(),
            (needed & (lo != hi)).sum(),
        ]).astype(jnp.int32)

    return jax.jit(audit)


class Single:
    """``PagedKVEngine`` behind this module's ``[hosts, ...]`` calls."""

    hosts = 1

    def __init__(self, geometry, gc, dtype, key):
        from repro.serve.engine import PagedKVEngine
        n, num_pages, ps, max_pages, hkv, hd = geometry
        self.eng = PagedKVEngine(n, num_pages, ps, max_pages, hkv, hd,
                                 gc=gc, dtype=dtype)
        # the K/V program closes over the ids, not over the adapter: a cycle
        # through it would keep the engine's state alive after the window
        seq = self.seq = jnp.arange(n, dtype=jnp.int32)
        self.kv = jax.jit(lambda s: ref.kv_rows(
            key, jnp.full((n,), s, jnp.int32), seq, hkv, hd, dtype))

    def step(self, k, v, mask) -> np.ndarray:
        return np.asarray(self.eng.step(self.seq, k, v,
                                        jnp.asarray(mask[0])))[None]

    def reset(self, mask) -> np.ndarray:
        return np.asarray(self.eng.reset(self.seq, jnp.asarray(mask[0])))[None]

    def reclaim(self) -> None:
        self.eng.reclaim()

    def idle_masks(self, none):
        """All-false masks as the driver and the engine's retries pass
        them, so that warming up compiles both."""
        return [none]

    def pin(self, h: int, lane: int) -> int:
        return self.eng.pin(lane)

    def unpin(self, h: int, lane: int) -> None:
        self.eng.unpin(lane)

    def view(self, h: int, t: int, seqs=None):
        return self.eng.view_at(t, seqs)

    def shard(self, h: int):
        return self.eng.st

    def now(self, h: int) -> int:
        return int(self.eng.st.mv.now)


class Sharded(Single):
    """``ShardedPagedKVEngine``, one shard per chip; K/V of sequence ``i``
    of host ``h`` is that of global sequence ``h * n + i``."""

    def __init__(self, geometry, gc, dtype, key, hosts: int,
                 stale_after_s: float):
        from jax.sharding import NamedSharding, PartitionSpec
        from repro.dist.mvgc import ShardedPagedKVEngine
        n, num_pages, ps, max_pages, hkv, hd = geometry
        self.hosts = hosts
        self.eng = ShardedPagedKVEngine(
            hosts, n, num_pages, ps, max_pages, hkv, hd,
            gc=gc.replace(stale_after_s=stale_after_s), dtype=dtype)
        spec = NamedSharding(self.eng.mesh,
                             PartitionSpec(self.eng.mesh.axis_names[0]))
        self.seq = jax.device_put(jnp.broadcast_to(
            jnp.arange(n, dtype=jnp.int32), (hosts, n)), spec)
        self.spec = spec
        every = jnp.arange(hosts * n, dtype=jnp.int32)

        def kv(s):
            k, v = ref.kv_rows(key, jnp.full((hosts * n,), s, jnp.int32),
                               every, hkv, hd, dtype)
            return (k.reshape(hosts, n, hkv, hd),
                    v.reshape(hosts, n, hkv, hd))

        self.kv = jax.jit(kv, out_shardings=(spec, spec))

    def step(self, k, v, mask) -> np.ndarray:
        return np.asarray(self.eng.step(self.seq, k, v, jnp.asarray(mask)))

    def reset(self, mask) -> np.ndarray:
        return np.asarray(self.eng.reset(self.seq, jnp.asarray(mask)))

    def idle_masks(self, none):
        # a retry passes the sharded mask of the lanes that failed
        return [none, jax.device_put(none, self.spec)]

    def pin(self, h: int, lane: int) -> int:
        return self.eng.pin(h, lane)

    def unpin(self, h: int, lane: int) -> None:
        self.eng.unpin(h, lane)

    def view(self, h: int, t: int, seqs=None):
        return self.eng.view_at(h, t, seqs)

    def shard(self, h: int):
        return self.eng.host_state(h)

    def now(self, h: int) -> int:
        return int(self.eng.st.mv.now[h])

    def lwm(self) -> int:
        """The global low-water mark the last GC refresh threaded into
        every shard's ops."""
        return int(self.eng.space()["lwm"])

    def stall(self, h: int) -> None:
        """Age ``h``'s announcements past the staleness budget."""
        ages = np.zeros((self.hosts,), np.float32)
        ages[h] = 10.0 * self.eng.gc.stale_after_s
        self.eng.virtual_ages_s = ages


def run(r: harness.Run) -> None:
    from repro.core.telemetry import GCConfig

    cfg, mix = r.config, r.traffic
    n = int(mix["num_seqs"])
    ps = int(cfg["kv_page_tokens"])
    num_pages = int(cfg["kv_pool_pages"])
    # the engine's pages have no layer axis: a page's head axis holds the
    # K/V heads of every layer on the chip, so the pool has the
    # deployment's bytes
    hkv = int(cfg["num_key_value_heads"]) * int(cfg["kv_pool_layers"])
    hd = int(cfg["head_dim"])
    max_pages = -(-int(mix["length_max"]) // ps)
    dtype = jnp.dtype(cfg["torch_dtype"])
    lanes = int(mix["pinned_lanes"])
    repin, reread = int(mix["repin_every"]), int(mix["reread_every"])
    audit_every, per_pin = int(mix["audit_every"]), int(mix["seqs_per_pin"])
    hosts = int(mix.get("hosts", 1))

    gc = GCConfig(policy=mix["policy"],
                  versions_per_slot=int(mix["versions_per_slot"]),
                  reader_lanes=int(mix["reader_lanes"]))
    key = ref.traffic_key(r.seed)
    geometry = (n, num_pages, ps, max_pages, hkv, hd)
    ad = (Single(geometry, gc, dtype, key) if hosts == 1 else
          Sharded(geometry, gc, dtype, key, hosts,
                  float(mix["stale_after_s"])))
    eng = ad.eng
    digest = jax.jit(ref.view_digest)
    audit = _audit_fn(num_pages, ps)

    # warm every program the traffic runs, on shapes it uses, without
    # appending: an all-false mask, an explicit reclaim pass, a pin, read
    # and audit on every host, each op also after a pin and an unpin
    none = np.zeros((hosts, n), bool)

    def idle_ops():
        for mask in ad.idle_masks(none):
            ad.step(*ad.kv(0), mask)
            ad.reset(mask)
        ad.reclaim()

    idle_ops()
    for h in range(hosts):
        t = ad.pin(h, 0)
        idle_ops()
        tb, ln = ad.view(h, t)
        tbs, lns = ad.view(h, t, jnp.arange(per_pin, dtype=jnp.int32))
        sh = ad.shard(h)
        jax.block_until_ready(digest(sh.k_pages, sh.v_pages, tbs, lns))
        # a state held across the engine's next op keeps a second pool
        # alive, and the pool fills half the chip
        del sh
        ad.unpin(h, 0)
        idle_ops()
        np.asarray(audit(ad.shard(h).free, tb[None], ln[None]))
        del tb, ln, tbs, lns
    jax.block_until_ready(eng.st)

    lengths = [Lengths(n, int(mix["length_min"]), int(mix["length_max"]),
                       r.seed) for _ in range(hosts)]
    logs = [ref.TrafficLog(n) for _ in range(hosts)]
    live = np.ones(hosts, bool)
    pins: Dict[tuple, Dict] = {}    # (host, lane) -> the lane's pin
    views: List[Dict] = []          # every read a pinned lane made
    audits: List[Dict] = []
    marks: List[Dict] = []          # the global LWM at each audit step
    pending = np.zeros((hosts, n), bool)   # finished, reset not yet acked
    gaps: List[float] = []
    counters = {"attempted": 0, "given_up": 0, "tokens": 0}

    def read(h: int, p: Dict, step: int, lane: int, fresh: bool) -> None:
        with harness.span("chipbench.reader"):
            tables, lens = ad.view(h, p["t"])
            tsub, lsub = ad.view(h, p["t"], p["seqs"])
            sh = ad.shard(h)
            dk, dv = digest(sh.k_pages, sh.v_pages, tsub, lsub)
            jax.block_until_ready((dk, dv))
        views.append({"host": h, "lane": lane, "pin_step": p["step"],
                      "step": step, "seqs": p["seqs_np"], "tables": tables,
                      "lens": lens, "dk": dk, "dv": dv})
        if fresh:
            p["tables"], p["lens"] = tables, lens

    def pin(h: int, lane: int, step: int) -> None:
        with harness.span("chipbench.reader"):
            if (h, lane) in pins:
                ad.unpin(h, lane)
            t = ad.pin(h, lane)
        # the longest sequences give the content check the most pages
        order = np.argsort(-lengths[h].length, kind="stable")[:per_pin]
        p = {"t": t, "step": step, "seqs_np": order.astype(np.int32),
             "seqs": jnp.asarray(order.astype(np.int32))}
        pins[(h, lane)] = p
        read(h, p, step, lane, fresh=True)

    def one_step(step: int, window) -> None:
        nonlocal pending
        if hosts > 1 and step == int(mix["stall_at"]):
            live[int(mix["stall_host"])] = False
            ad.stall(int(mix["stall_host"]))
        t0 = time.perf_counter()
        mask = live[:, None] & ~pending
        with harness.span("chipbench.append"):
            k, v = ad.kv(step)
            failed = ad.step(k, v, mask)
        acked = mask & ~failed
        done = np.stack([ln.advance(a) for ln, a in zip(lengths, acked)])
        done |= pending
        reset_ok = np.zeros((hosts, n), bool)
        if done.any():
            with harness.span("chipbench.reset"):
                rfail = ad.reset(done)
            reset_ok = done & ~rfail
            pending = done & rfail
            for h in range(hosts):
                lengths[h].restart(reset_ok[h])
        jax.block_until_ready(eng.st)
        t1 = time.perf_counter()
        for h in range(hosts):
            logs[h].record(acked[h], reset_ok[h])
        if window is not None:
            gaps.append(t1 - t0)
            counters["attempted"] += int(mask.sum())
            counters["given_up"] += int((mask & failed).sum())
            counters["tokens"] += int(acked.sum())
        audit_due = (window is not None
                     and step % audit_every == audit_every - 1)
        # the pins the step's last LWM refresh saw: those before its re-pins
        standing = [(g, p["t"]) for (g, _), p in pins.items()]
        for h in range(hosts):
            for lane in range(lanes):
                off = (lane * repin // lanes
                       + h * repin // (lanes * hosts))
                if (live[h] and step >= off
                        and (step - off) % repin == 0):
                    pin(h, lane, step)
                elif (h, lane) in pins and (step - off) % reread == 0:
                    read(h, pins[(h, lane)], step, lane, fresh=False)
        if audit_due:
            with window.stopped():
                audit_all(step)
                if hosts > 1:
                    marks.append({"step": step, "live": live.copy(),
                                  "pins": standing, "lwm": ad.lwm()})

    def audit_all(step: int) -> None:
        for h in range(hosts):
            tables, lens = ad.view(h, ad.now(h))
            mine = [p for (g, _), p in pins.items() if g == h]
            live_t = [tables] + [p["tables"] for p in mine]
            live_l = [lens] + [p["lens"] for p in mine]
            counts = np.asarray(audit(ad.shard(h).free, jnp.stack(live_t),
                                      jnp.stack(live_l)))
            audits.append({"host": h, "step": step,
                           "lens": np.asarray(lens), "counts": counts})

    step = 0
    for _ in range(int(mix["warm_steps"])):
        one_step(step, None)
        step += 1
    # compiles the audit for the number of views the window's audits stack,
    # and the read of the global LWM; the warm phase's reads and audits are
    # not checked
    audit_all(step - 1)
    if hosts > 1:
        ad.lwm()
    views.clear()
    audits.clear()
    r.setup_done()
    first = step
    with harness.Window(r) as w:
        while w.elapsed() < r.seconds:
            one_step(step, w)
            step += 1
    held = {}
    for a in audits:
        c = held.setdefault(a["step"], np.zeros(2, np.int64))
        c += a["counts"][:2]
    r.obs.update({
        "window_s": w.elapsed(),
        "steps": step - first,
        "gaps_s": gaps,
        "tokens": counters["tokens"],
        "held_ratio": [c[0] / max(1, c[1]) for c in held.values()],
        "shapes": {"versions": gc.versions_per_slot, "max_pages": max_pages},
    })
    r.attempted = counters["attempted"]
    r.failed = counters["given_up"]
    r.obs["device"] = harness.device_info(jax.devices()[:r.chips])

    # free the program's state before the reference runs
    views_host = [dict(vw, tables=np.asarray(vw["tables"]),
                       lens=np.asarray(vw["lens"]),
                       dk=np.asarray(vw["dk"]), dv=np.asarray(vw["dv"]))
                  for vw in views]
    del ad, eng, pins, views
    ref.compare(r, logs, views_host, audits, key=key, page_size=ps,
                max_pages=max_pages, hkv=hkv, hd=hd, dtype=dtype,
                marks=marks if hosts > 1 else None)
