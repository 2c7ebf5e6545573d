"""Production mesh builders.

Functions, not module-level constants: importing this module never touches
jax device state (the dry-run sets XLA_FLAGS before any jax import)."""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (16, 16) = 256 chips, axes (data, model).
    Multi-pod:  (2, 16, 16) = 512 chips, axes (pod, data, model) — the pod
    axis crosses the DCN; gradient reduction over it is what the int8
    compression path targets."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(
        shape, axes,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
    )


def make_host_mesh(data: int = 1, model: int = 1):
    """Tiny mesh over however many local devices exist (tests/examples)."""
    n = len(jax.devices())
    data = min(data, n)
    return jax.make_mesh(
        (data, max(1, min(model, n // data))), ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2,
    )


def make_gc_mesh(hosts: int = 0, axis: str = "gc_hosts"):
    """1-D mesh for the sharded MVGC stack (``repro.dist.mvgc``): one
    position per host along ``axis``.  ``hosts=0`` uses every available
    device.  The global-LWM ring all-reduce and the per-shard GC shard_maps
    both run over this axis (DESIGN.md §13).

    More hosts than devices is a mesh of every device, each holding
    ``hosts / devices`` shards, so the device count must divide ``hosts``;
    anything else raises rather than running on fewer devices.  A single
    device gives the one-position mesh on which the stack stays unsharded."""
    n = len(jax.devices())
    size = n if hosts <= 0 else min(hosts, n)
    if hosts > n > 1 and hosts % n:
        raise ValueError(
            f"{hosts} MVGC hosts cannot be laid out over {n} devices: the "
            f"device count must divide the host count")
    return jax.make_mesh(
        (size,), (axis,), axis_types=(jax.sharding.AxisType.Auto,),
    )
