#!/usr/bin/env python3
"""Smoke run of the MVGC serving path on a TPU: the quickest proof that the
system still starts on the chip.

    python chip_smoke.py               # one chip: phases A and B
    python chip_smoke.py --four-chips  # four chips: the sharded path only

Phase A serves gemma2-2b at its published width (bfloat16, random weights
from ``--seed``) through ``repro.launch.serve``: 8 requests of 512 prompt
tokens, 32 greedy decode steps each, with snapshot readers pinned mid-decode
whose lengths must not move.  The decode step must run the GC kernels
compiled (``tpu_custom_call`` in its HLO).  A float32 reference check on a
small config compares the served tokens with a teacher-forced forward pass.

Phase B runs ``PagedKVEngine`` with gemma2-2b's KV geometry (4 KV heads of
256, bfloat16, 16-token pages) over a 65536-page pool, about a quarter of a
16 GB chip, oversubscribed by 1024 sequences whose target lengths are
uniform in 256-1280 tokens, reset on completion.  Four reader lanes stay
pinned throughout, and every pinned ``view_at`` view (page tables, lengths
and the K/V bytes under them) must stay identical while its pin is held.
It runs until at least 3 pressure events have reclaimed pages.

``--four-chips`` runs ``ShardedPagedKVEngine`` with four hosts at phase B's
geometry, one host per chip, with host 3 stalled and aged out through
``virtual_ages_s``, and then replays shard 0's traffic on the single-host
engine: free-page counts and pinned views must match step for step.

Every phase raises on failure.  Without a TPU the script exits non-zero
before any phase.  The last line of a passing run is one JSON object naming
the device.
"""
from __future__ import annotations

import argparse
import gc as _gc
import hashlib
import json
import sys
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

# gemma2-2b's KV geometry and phase B's pool (see the module docstring)
KV_HEADS, HEAD_DIM, PAGE_SIZE = 4, 256, 16
NUM_SEQS, NUM_PAGES, MAX_PAGES = 1024, 65536, 80
LENGTHS = (256, 1280)


class Fail(RuntimeError):
    """A phase's check failed."""


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise Fail(msg)


def _peak_bytes() -> Optional[int]:
    stats = jax.devices()[0].memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# phase A: serve a model through repro.launch.serve
# ---------------------------------------------------------------------------
def phase_serve(cfg, *, batch: int = 8, prompt_len: int = 512,
                steps: int = 32, max_len: int = 1024, seed: int = 0,
                gc=None, log: Callable[[str], None] = print) -> Dict:
    """Serve ``batch`` requests on ``cfg`` with snapshot readers pinned
    mid-decode; every pinned reader's lengths must be unchanged at the end.
    On a TPU the decode step must hold the compiled GC kernels."""
    from repro.configs.base import SHAPES, RunConfig
    from repro.core.telemetry import GCConfig
    from repro.launch.serve import build_engine, make_prompts, serve

    gc = gc or GCConfig(policy="slrt", versions_per_slot=16, reader_lanes=8)
    run = RunConfig(model=cfg, shape=SHAPES["decode_32k"], gc=gc)
    t0 = time.perf_counter()
    engine = build_engine(cfg, run, batch, max_len, seed)
    jax.block_until_ready(engine.state)
    init_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    hlo = engine.compile_decode().as_text()
    compile_s = time.perf_counter() - t0
    custom_call = "tpu_custom_call" in hlo
    if _on_tpu():
        _check(custom_call, "decode step holds no tpu_custom_call: the GC "
                            "kernels did not compile into it")
    out = serve(engine, make_prompts(cfg, batch, prompt_len, seed), steps,
                pin_every=8, log=lambda s: None)

    toks = out["tokens"]
    _check(toks.shape == (batch, steps), f"tokens shape {toks.shape}")
    _check(bool(((toks >= 0) & (toks < cfg.vocab_size)).all()),
           "token ids outside the vocabulary")
    _check(len(out["readers"]) >= 2, "fewer than 2 snapshot readers pinned")
    for lane, r in out["readers"].items():
        _check(np.array_equal(r["lengths_at_pin"], r["lengths_at_end"]),
               f"reader lane {lane}: lengths moved under the pin "
               f"{r['lengths_at_pin']} -> {r['lengths_at_end']}")
        # a lane pinned after decode step i sees prompt_len + i + 1 tokens
        want = prompt_len + 8 * lane + 1
        _check(bool((r["lengths_at_pin"] == want).all()),
               f"reader lane {lane}: lengths {r['lengths_at_pin']} != {want}")
    _check(engine.last_stats["retry_failed"] == 0, "descriptor retry failed")
    res = {
        "model": cfg.name, "d_model": cfg.d_model, "layers": cfg.num_layers,
        "param_dtype": run.param_dtype, "cache_dtype": run.dtype,
        "requests": batch, "prompt_len": prompt_len, "decode_steps": steps,
        "init_s": init_s, "decode_compile_s": compile_s,
        "prefill_s_incl_compile": out["prefill_s"],
        "decode_s_per_step": float(np.mean(out["step_s"][1:])),
        "tpu_custom_call": custom_call,
        "pinned_readers": len(out["readers"]),
        "live_versions": out["space"]["live_versions"],
        "peak_bytes_in_use": _peak_bytes(),
    }
    log(f"[phase A] {json.dumps(res)}")
    return res


def phase_serve_reference(cfg, *, batch: int = 2, prompt_len: int = 24,
                          steps: int = 6, seed: int = 1, gc=None,
                          log: Callable[[str], None] = print) -> Dict:
    """float32 reference on a small input: every served token must equal the
    argmax of a teacher-forced ``transformer.forward`` over the prompt and
    the tokens served before it."""
    from repro.configs.base import SHAPES, RunConfig
    from repro.core.telemetry import GCConfig
    from repro.launch.serve import build_engine, make_prompts, serve
    from repro.models import transformer as tf

    gc = gc or GCConfig(policy="slrt", versions_per_slot=16, reader_lanes=8)
    run = RunConfig(model=cfg, shape=SHAPES["decode_32k"], gc=gc,
                    dtype="float32", param_dtype="float32")
    with jax.default_matmul_precision("highest"):
        engine = build_engine(cfg, run, batch, prompt_len + steps + 1, seed)
        prompts = make_prompts(cfg, batch, prompt_len, seed)
        served = serve(engine, prompts, steps, pin_every=0,
                       log=lambda s: None)["tokens"]
        fwd = jax.jit(lambda p, t: tf.forward(p, cfg, t, remat=False)[0])

        def greedy(ctx):
            return np.asarray(jnp.argmax(
                fwd(engine.state.params, jnp.asarray(ctx))[:, -1], -1))

        # prefill picks the token after the prompt; decode step i serves
        # the one after that, teacher-forced on what was served before it
        ctx = np.asarray(prompts)
        ctx = np.concatenate([ctx, greedy(ctx)[:, None]], 1)
        mism = 0
        for i in range(steps):
            mism += int((greedy(ctx) != served[:, i]).sum())
            ctx = np.concatenate([ctx, served[:, i:i + 1]], 1)
    _check(mism == 0, f"{mism} served tokens differ from the forward pass")
    res = {"model": cfg.name, "tokens_compared": int(served.size),
           "mismatches": mism}
    log(f"[phase A ref] {json.dumps(res)}")
    return res


# ---------------------------------------------------------------------------
# phase B and --four-chips: the paged MVGC cache under pressure
# ---------------------------------------------------------------------------
class Traffic:
    """Host-side request schedule: each sequence draws a target length
    uniform in ``[lo, hi]`` tokens, is reset when it reaches it, and draws
    again."""

    def __init__(self, num_seqs: int, lo: int, hi: int, seed: int):
        self.rng = np.random.default_rng(seed)
        self.lo, self.hi = lo, hi
        self.target = self.rng.integers(lo, hi + 1, num_seqs)
        self.length = np.zeros(num_seqs, np.int64)

    def advance(self, appended: np.ndarray) -> np.ndarray:
        """Count the appended tokens; returns the sequences now complete."""
        self.length += appended
        done = self.length >= self.target
        self.length[done] = 0
        self.target[done] = self.rng.integers(self.lo, self.hi + 1,
                                              int(done.sum()))
        return done


def _view_content(k_pages, v_pages, tables, lengths):
    """The K and V bytes a view exposes: ``[n, MP * PS, Hkv, D]`` each, as
    unsigned words of the pool's width, zero past each sequence's length."""
    n, mp = tables.shape
    ps = k_pages.shape[1]
    keep = jnp.arange(mp * ps)[None, :] < lengths[:, None]
    word = jnp.uint16 if k_pages.dtype.itemsize == 2 else jnp.uint32

    def take(pages):
        x = pages[jnp.maximum(tables, 0)].reshape(n, mp * ps,
                                                  *pages.shape[2:])
        x = jax.lax.bitcast_convert_type(x, word)
        return jnp.where(keep[:, :, None, None], x, 0)

    return take(k_pages), take(v_pages)


def _random_kv(key, step, shape, dtype):
    kk, kv = jax.random.split(jax.random.fold_in(key, step))
    return (jax.random.normal(kk, shape, jnp.float32).astype(dtype),
            jax.random.normal(kv, shape, jnp.float32).astype(dtype))


class _SingleHost:
    """``PagedKVEngine`` driven through ``[H, ...]`` arrays with H = 1."""

    hosts = 1

    def __init__(self, eng):
        self.eng = eng
        self.seq = jnp.arange(eng.st.mv.store.ts.shape[0], dtype=jnp.int32)
        self._content = jax.jit(_view_content)

    def step(self, k, v, mask):
        failed = self.eng.step(self.seq, k, v, jnp.asarray(mask[0]))
        return np.asarray(failed)[None]

    def reset(self, done):
        self.eng.reset(self.seq, jnp.asarray(done[0]))

    def pin(self, host, lane):
        return self.eng.pin(lane)

    def unpin(self, host, lane):
        self.eng.unpin(lane)

    def view(self, host, t, seqs=None):
        return self.eng.view_at(t, seqs)

    def content(self, tables, lengths):
        k, v = self._content(self.eng.st.k_pages, self.eng.st.v_pages,
                             tables[0], lengths[0])
        return k[None], v[None]

    def free_pages(self):
        return np.asarray(self.eng.st.free.sum())[None]

    def kv_fn(self, key):
        _, _, hkv, d = self.eng.st.k_pages.shape
        shape, dtype = (self.seq.shape[0], hkv, d), self.eng.st.k_pages.dtype
        return jax.jit(lambda step: _random_kv(key, step, shape, dtype))


class _Sharded:
    """``ShardedPagedKVEngine``, whose arguments are already ``[H, ...]``."""

    def __init__(self, eng):
        from jax.sharding import NamedSharding, PartitionSpec
        self.eng = eng
        self.hosts = eng.hosts
        self.spec = NamedSharding(eng.mesh,
                                  PartitionSpec(eng.mesh.axis_names[0]))
        n = eng.st.mv.store.ts.shape[1]
        self.seq = jax.device_put(
            jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), (self.hosts, n)),
            self.spec)
        self._content = jax.jit(jax.vmap(_view_content))

    def step(self, k, v, mask):
        return np.asarray(self.eng.step(self.seq, k, v, jnp.asarray(mask)))

    def reset(self, done):
        self.eng.reset(self.seq, jnp.asarray(done))

    def pin(self, host, lane):
        return self.eng.pin(host, lane)

    def unpin(self, host, lane):
        self.eng.unpin(host, lane)

    def view(self, host, t, seqs=None):
        return self.eng.view_at(host, t, seqs)

    def content(self, tables, lengths):
        return self._content(self.eng.st.k_pages, self.eng.st.v_pages,
                             tables, lengths)

    def free_pages(self):
        return np.asarray(self.eng.st.free.sum(axis=1))

    def stall(self, host):
        """Age ``host``'s announcements past the staleness budget."""
        ages = np.zeros((self.hosts,), np.float32)
        ages[host] = 10.0 * self.eng.gc.stale_after_s
        self.eng.virtual_ages_s = ages

    def kv_fn(self, key):
        _, _, _, hkv, d = self.eng.st.k_pages.shape
        shape = (self.seq.shape[1], hkv, d)
        dtype, hosts = self.eng.st.k_pages.dtype, self.hosts

        def kv(step):
            k, v = _random_kv(key, step, shape, dtype)
            return (jnp.broadcast_to(k[None], (hosts,) + shape),
                    jnp.broadcast_to(v[None], (hosts,) + shape))

        return jax.jit(kv, out_shardings=(self.spec, self.spec))


def drive(adapter, *, lengths=LENGTHS, lanes: int = 4, hold: int = 96,
          check_every: int = 32, seqs_per_pin: int = 8,
          min_reclaiming: int = 3, steps: Optional[int] = None,
          max_steps: int = 1000, stall: Optional[tuple] = None,
          seed: int = 0) -> Dict:
    """Run request traffic through a paged engine with reader lanes pinned
    throughout, checking every pinned view while its pin is held.

    Every live host runs the same traffic (one schedule from ``seed``), so
    shard 0 of a sharded run can be replayed on a single host.  Lane ``L``
    re-pins every ``hold`` steps, offset by ``L * hold / lanes``: after its
    first pin a lane is never unpinned for longer than the re-pin itself.
    Every pinned lane holds one version of every sequence, so a slab of V
    versions fits at most V - 2 lanes pinned at distinct times (beside the
    current version and the one being written); past that every append
    fails and the sequences never complete.
    The run stops after ``steps`` steps if given, else once
    ``min_reclaiming`` pressure events have reclaimed pages.  ``stall =
    (host, step)`` stops a host's traffic and pin updates at that step and
    ages its announcements out.  Returns the summary and host 0's trace:
    its free-page count after every step and a digest of every view it
    checked."""
    eng, hosts = adapter.eng, adapter.hosts
    num_seqs = adapter.seq.shape[-1]
    traffic = Traffic(num_seqs, *lengths, seed)
    kv = adapter.kv_fn(jax.random.PRNGKey(seed))
    live = np.ones((hosts,), bool)
    pins: Dict[int, Dict] = {}
    trace = {"free": [], "views": []}
    checks = violations = reclaiming = 0
    step_s: List[float] = []

    def views(lane, seqs=None):
        got = [adapter.view(h, int(pins[lane]["t"][h]), seqs)
               for h in range(hosts)]
        return (np.stack([np.asarray(g[0]) for g in got]),
                np.stack([np.asarray(g[1]) for g in got]))

    def check(lane):
        nonlocal checks, violations
        p = pins[lane]
        tables, lens = views(lane)
        k, v = adapter.content(*views(lane, p["seqs"]))
        same = (np.asarray(jnp.all(k == p["k"], axis=(1, 2, 3, 4)))
                & np.asarray(jnp.all(v == p["v"], axis=(1, 2, 3, 4)))
                & (tables == p["tables"]).all(axis=(1, 2))
                & (lens == p["lens"]).all(axis=1))
        checks += hosts
        violations += int((~same).sum())
        trace["views"].append(hashlib.sha256(
            tables[0].tobytes() + lens[0].tobytes()).hexdigest())

    def pin(lane):
        old = pins.get(lane)
        t = np.zeros((hosts,), np.int64)
        for h in range(hosts):
            if live[h] or old is None:
                if old is not None:
                    adapter.unpin(h, lane)
                t[h] = adapter.pin(h, lane)
            else:
                t[h] = old["t"][h]       # a stalled host keeps its pin
        # the longest sequences give the content check the most pages
        seqs = jnp.asarray(np.argsort(-traffic.length, kind="stable")
                           [:seqs_per_pin].astype(np.int32))
        pins[lane] = {"t": t, "seqs": seqs}
        pins[lane]["tables"], pins[lane]["lens"] = views(lane)
        pins[lane]["k"], pins[lane]["v"] = adapter.content(
            *views(lane, seqs))

    t_start = time.perf_counter()
    step = 0
    while True:
        if steps is not None and step >= steps:
            break
        if steps is None and reclaiming >= min_reclaiming:
            break
        _check(step < max_steps,
               f"{reclaiming} reclaiming pressure events after {max_steps} "
               f"steps ({eng.stats.pressure_events} pressure events, "
               f"{eng.stats.give_ups} lanes given up)")
        if stall is not None and step == stall[1]:
            live[stall[0]] = False
            adapter.stall(stall[0])
        t0 = time.perf_counter()
        ev0, rec0 = eng.stats.pressure_events, eng.stats.reclaimed
        mask = np.broadcast_to(live[:, None], (hosts, num_seqs))
        failed = adapter.step(*kv(step), mask)
        _check(bool((failed[live] == failed[0]).all()),
               "live hosts ran the same traffic but failed differently")
        done = traffic.advance(~failed[0])
        if done.any():
            adapter.reset(mask & done[None, :])
        if (eng.stats.pressure_events > ev0
                and eng.stats.reclaimed > rec0):
            reclaiming += 1
        for lane in range(lanes):
            off = lane * hold // lanes
            if step >= off and (step - off) % hold == 0:
                if lane in pins:
                    check(lane)
                pin(lane)
        if step % check_every == check_every - 1:
            for lane in pins:
                check(lane)
        trace["free"].append(adapter.free_pages()[0])
        step_s.append(time.perf_counter() - t0)
        step += 1
    for lane in pins:
        check(lane)
    elapsed = time.perf_counter() - t_start
    res = {
        "hosts": hosts, "steps": step, "seconds": elapsed,
        "step_s_mean": float(np.mean(step_s)),
        "pressure_events": eng.stats.pressure_events,
        "reclaims_triggered": eng.stats.reclaims_triggered,
        "pages_reclaimed": eng.stats.reclaimed,
        "reclaiming_events": reclaiming,
        "peak_pages": eng.stats.peak_live,
        "give_ups": eng.stats.give_ups,
        "pin_checks": checks, "pin_violations": violations,
    }
    return {"summary": res, "trace": trace}


def _paged_gc(**kw):
    from repro.core.telemetry import GCConfig
    return GCConfig(policy="slrt", **kw)


def phase_paged(*, num_seqs: int = NUM_SEQS, num_pages: int = NUM_PAGES,
                page_size: int = PAGE_SIZE, max_pages: int = MAX_PAGES,
                kv_heads: int = KV_HEADS, head_dim: int = HEAD_DIM,
                dtype=jnp.bfloat16, gc=None, seed: int = 0,
                log: Callable[[str], None] = print, **drive_kw) -> Dict:
    """The paged cache at a real share of the chip: at least 3 reclaiming
    pressure events with reader lanes pinned and 0 pinned-view
    violations."""
    from repro.serve.engine import PagedKVEngine

    gc = gc or _paged_gc()
    eng = PagedKVEngine(num_seqs, num_pages, page_size, max_pages, kv_heads,
                        head_dim, gc=gc, dtype=dtype)
    out = drive(_SingleHost(eng), seed=seed, **drive_kw)
    res = dict(out["summary"], num_pages=num_pages, num_seqs=num_seqs,
               pool_bytes=2 * eng.st.k_pages.nbytes,
               peak_bytes_in_use=_peak_bytes())
    log(f"[phase B] {json.dumps(res)}")
    _check(res["reclaiming_events"] >= drive_kw.get("min_reclaiming", 3),
           "too few pressure events reclaimed pages")
    _check(res["pin_checks"] > 0, "no pinned view was checked")
    _check(res["pin_violations"] == 0,
           f"{res['pin_violations']} pinned views changed under their pin")
    return res


def phase_four_chips(*, hosts: int = 4, num_seqs: int = NUM_SEQS,
                     num_pages: int = NUM_PAGES, page_size: int = PAGE_SIZE,
                     max_pages: int = MAX_PAGES, kv_heads: int = KV_HEADS,
                     head_dim: int = HEAD_DIM, dtype=jnp.bfloat16, gc=None,
                     stall_step: int = 24, seed: int = 0,
                     log: Callable[[str], None] = print, **drive_kw) -> Dict:
    """The sharded engine on a ``hosts``-device mesh, one shard per device
    at phase B's geometry, with the last host stalled; then shard 0's
    traffic replayed on the single-host engine must free the same pages
    and show the same pinned views, step for step."""
    from repro.dist.mvgc import ShardedPagedKVEngine, lwm_contributions
    from repro.serve.engine import PagedKVEngine

    gc = (gc or _paged_gc()).replace(stale_after_s=5.0)
    eng = ShardedPagedKVEngine(hosts, num_seqs, num_pages, page_size,
                               max_pages, kv_heads, head_dim, gc=gc,
                               dtype=dtype)
    _check(eng._ring is not None, "the global-LWM ring was not built")
    for path, leaf in jax.tree_util.tree_leaves_with_path(eng.st):
        _check(len(leaf.sharding.device_set) == hosts
               and leaf.addressable_shards[0].data.shape[0] == 1,
               f"state leaf {jax.tree_util.keystr(path)} is not sharded "
               f"over {hosts} devices: {leaf.sharding}")
    ring_hlo = eng._ring.lower(lwm_contributions(eng.st)).compile().as_text()
    permutes = (ring_hlo.count("collective-permute-start")
                or ring_hlo.count("collective-permute("))
    _check(permutes > 0, "the ring all-reduce holds no collective-permute")

    out = drive(_Sharded(eng), stall=(hosts - 1, stall_step), seed=seed,
                **drive_kw)
    res = dict(out["summary"], lwm_advances=eng.lwm_advances,
               stale_lanes_aged=eng.stats.stale_lanes_aged,
               ring_collective_permutes=permutes,
               devices=len(eng.mesh.devices.flat),
               peak_bytes_in_use=_peak_bytes())
    log(f"[four chips] {json.dumps(res)}")
    _check(res["pin_violations"] == 0,
           f"{res['pin_violations']} pinned views changed under their pin")
    _check(res["lwm_advances"] > 0, "the global LWM never advanced")
    _check(res["stale_lanes_aged"] > 0, "the stalled host was never aged")
    del eng
    _gc.collect()

    single = PagedKVEngine(num_seqs, num_pages, page_size, max_pages,
                           kv_heads, head_dim, gc=gc, dtype=dtype)
    kw = dict(drive_kw, steps=res["steps"])
    replay = drive(_SingleHost(single), seed=seed, **kw)
    same_free = replay["trace"]["free"] == out["trace"]["free"]
    same_views = replay["trace"]["views"] == out["trace"]["views"]
    cmp = {"steps_compared": len(out["trace"]["free"]),
           "views_compared": len(out["trace"]["views"]),
           "free_pages_match": same_free, "views_match": same_views,
           "replay_pin_violations": replay["summary"]["pin_violations"]}
    log(f"[four chips vs single host] {json.dumps(cmp)}")
    _check(same_free, "shard 0 and its single-host replay freed different "
                      "pages")
    _check(same_views, "shard 0 and its single-host replay show different "
                       "pinned views")
    return dict(res, replay=cmp)


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------
class _CacheEvents:
    """Counts persistent compilation cache hits and misses."""

    def __init__(self):
        from jax import monitoring
        self.hits = self.misses = 0
        monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded path, on four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    print(f"[start] jax {jax.__version__} platform={dev.platform} "
          f"device_kind={dev.device_kind} count={len(devices)}", flush=True)
    if dev.platform != "tpu":
        print("chip_smoke: no TPU is attached; this run needs one",
              file=sys.stderr)
        return 1
    if args.four_chips and len(devices) < 4:
        print(f"chip_smoke: --four-chips needs 4 chips, found {len(devices)}",
              file=sys.stderr)
        return 1

    from repro.configs import get_config, reduced_config
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    events = _CacheEvents()
    print(f"[start] compile cache {cache}", flush=True)
    log = lambda s: print(s, flush=True)  # noqa: E731
    t0 = time.perf_counter()
    if args.four_chips:
        phase_four_chips(seed=args.seed, log=log)
    else:
        phase_serve(get_config("gemma2-2b"), seed=args.seed, log=log)
        phase_serve_reference(reduced_config("gemma2-2b"), log=log)
        _gc.collect()
        phase_paged(seed=args.seed, log=log)
    print(f"[done] {time.perf_counter() - t0:.1f}s; compile cache hits "
          f"{events.hits} misses {events.misses}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
