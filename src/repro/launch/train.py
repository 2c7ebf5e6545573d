"""Training driver: data -> jitted train_step -> checkpoints, with the full
fault-tolerance loop (watchdog, heartbeat, restart-from-latest, MVGC
checkpoint retention).

Local run (CPU, reduced config):
  PYTHONPATH=src python -m repro.launch.train --arch minitron-4b --reduced \
      --steps 50 --ckpt-dir /tmp/ckpt
Pod run: launched per host by launch_pod.sh with jax.distributed.initialize.
"""
from __future__ import annotations

import argparse
import functools
import os
import time

import jax
import jax.numpy as jnp

from repro.ckpt.manager import CheckpointManager
from repro.configs import get_config, reduced_config
from repro.configs.base import RunConfig, SHAPES
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.dist.straggler import HeartbeatFile, StepWatchdog
from repro.launch.compile_cache import enable_compile_cache
from repro.train.step import TrainState, init_state, train_step


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="tiny same-family config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--grad-compression", action="store_true")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--log-every", type=int, default=5)
    ap.add_argument("--simulate-crash-at", type=int, default=-1,
                    help="abort at this step (fault-tolerance demo)")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = reduced_config(args.arch) if args.reduced else get_config(args.arch)
    run = RunConfig(model=cfg, shape=SHAPES["train_4k"], lr=args.lr,
                    microbatches=args.microbatches,
                    grad_compression=args.grad_compression)
    data = SyntheticLM(DataConfig(cfg.vocab_size, args.seq_len, args.batch))
    mgr = CheckpointManager(args.ckpt_dir)
    watchdog = StepWatchdog()
    hb = HeartbeatFile(os.path.join(args.ckpt_dir, "heartbeat.json"),
                       host_id=jax.process_index())

    state = init_state(cfg, jax.random.PRNGKey(0),
                       compression=args.grad_compression)
    start = 0
    latest = mgr.latest_step()
    if latest is not None:
        state_raw, extra = mgr.restore(latest, like=state)
        state = TrainState(*state_raw)
        data.load_state_dict(extra)
        start = latest
        print(f"[restore] resumed from step {latest}")

    step_fn = jax.jit(functools.partial(train_step, cfg=cfg, run=run))
    for i in range(start, args.steps):
        watchdog.start()
        batch = {k: jnp.asarray(v) for k, v in next(data).items()}
        state, metrics = step_fn(state, batch)
        dt = watchdog.stop(i)
        hb.beat(i)
        if i % args.log_every == 0 or i == args.steps - 1:
            print(f"step {i:5d}  loss {float(metrics['loss']):.4f}  "
                  f"lr {float(metrics['lr']):.2e}  "
                  f"gnorm {float(metrics['grad_norm']):.3f}  {dt*1e3:.0f}ms")
        if args.simulate_crash_at == i:
            print(f"[crash] simulated failure at step {i}")
            raise SystemExit(42)
        if (i + 1) % args.ckpt_every == 0 or i == args.steps - 1:
            path = mgr.save(i + 1, state, extra=data.state_dict())
            deleted = mgr.gc(keep_last=2)
            print(f"[ckpt] saved {path}"
                  + (f"; MVGC reclaimed {deleted}" if deleted else ""))
    if watchdog.suspect_steps:
        print(f"[straggler] suspect steps: {watchdog.suspect_steps}")


if __name__ == "__main__":
    main()
