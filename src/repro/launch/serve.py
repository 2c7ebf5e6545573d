"""Serving driver: MV-Serve engine with batched requests + snapshot readers.

Local run (CPU, reduced config):
  PYTHONPATH=src python -m repro.launch.serve --arch gemma2-2b --reduced \
      --batch 4 --steps 32 --gc-policy slrt

Weights are random, made from a seed, at ``RunConfig.param_dtype``; the KV
cache is held at ``RunConfig.dtype`` (both bfloat16 by default).
"""
from __future__ import annotations

import argparse
import functools
import time
from typing import Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, reduced_config
from repro.configs.base import ModelConfig, RunConfig, SHAPES
from repro.launch.compile_cache import enable_compile_cache
from repro.models import transformer as tf
from repro.serve.engine import MVServeEngine

MAX_READERS = 4   # snapshot readers pinned per run


def build_engine(cfg: ModelConfig, run: RunConfig, batch: int, max_len: int,
                 seed: int = 0) -> MVServeEngine:
    """An engine over random weights from ``seed``: parameters at
    ``run.param_dtype``, made on the device in one program, and the cache
    at ``run.dtype``."""
    init = jax.jit(functools.partial(tf.init_params, cfg,
                                     dtype=jnp.dtype(run.param_dtype)))
    params = init(jax.random.PRNGKey(seed))
    return MVServeEngine(cfg, run, params, batch=batch, max_len=max_len,
                         dtype=jnp.dtype(run.dtype))


def make_prompts(cfg: ModelConfig, batch: int, prompt_len: int,
                 seed: int = 0) -> jax.Array:
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, prompt_len)),
                       jnp.int32)


def serve(engine: MVServeEngine, prompts: jax.Array, steps: int,
          pin_every: int = 8, log: Callable[[str], None] = print) -> Dict:
    """Prefill ``prompts`` and decode ``steps`` greedy tokens per request.
    Every ``pin_every`` steps (up to ``MAX_READERS``) a snapshot reader pins
    the next lane; each pin's visible lengths are read when it is taken and
    again after the last step, then it is released.

    Times end in ``block_until_ready``.  The first decode step includes its
    compilation unless the caller compiled it beforehand."""
    t0 = time.perf_counter()
    engine.prefill(prompts)
    jax.block_until_ready(engine.state)
    prefill_s = time.perf_counter() - t0
    log(f"[prefill] {prompts.shape[0]}x{prompts.shape[1]} in {prefill_s:.3f}s")

    tokens, step_s, pins = [], [], {}
    for i in range(steps):
        t0 = time.perf_counter()
        toks = jax.block_until_ready(engine.step())
        step_s.append(time.perf_counter() - t0)
        tokens.append(toks)
        if pin_every and i % pin_every == 0 and len(pins) < MAX_READERS:
            lane = len(pins)
            t = engine.pin(lane)
            pins[lane] = (t, np.asarray(engine.lengths_at(t)))
            log(f"[rtx] lane {lane} pinned t={t}")
        if i % 8 == 0:
            rep = engine.space()
            log(f"step {i:3d}  tokens {np.asarray(toks[:, 0])[:4]}  "
                f"live_versions {rep['live_versions']}  "
                f"ring {rep['ring_size']}  overflow {rep['overflows']}")
    readers = {}
    for lane, (t, at_pin) in pins.items():
        at_end = np.asarray(engine.lengths_at(t))
        readers[lane] = {"t": t, "lengths_at_pin": at_pin,
                         "lengths_at_end": at_end}
        log(f"[rtx] lane {lane} snapshot@{t}: lengths {at_end}")
        engine.unpin(lane)
    return {
        "tokens": np.concatenate([np.asarray(t) for t in tokens], axis=1),
        "prefill_s": prefill_s,
        "step_s": step_s,
        "readers": readers,
        "space": engine.space(),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--steps", type=int, default=32)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--gc-policy", default="slrt",
                    choices=["slrt", "dlrt", "steam", "ebr", "sweep"])
    ap.add_argument("--pin-every", type=int, default=8,
                    help="start a snapshot reader every N steps")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = reduced_config(args.arch) if args.reduced else get_config(args.arch)
    run = RunConfig(model=cfg, shape=SHAPES["decode_32k"],
                    gc_policy=args.gc_policy, versions_per_slot=16,
                    reader_lanes=8)
    engine = build_engine(cfg, run, args.batch, args.max_len)
    out = serve(engine, make_prompts(cfg, args.batch, args.prompt_len),
                args.steps, pin_every=args.pin_every)
    print(f"[done] decode {np.mean(out['step_s'][1:]):.4f}s/step after the "
          f"first  space report: {out['space']}")


if __name__ == "__main__":
    main()
