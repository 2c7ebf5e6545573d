"""Print the StableHLO of `PagedKVEngine`'s programs at a small geometry.

A change to the engine's host loop that must leave its device programs as
they are is checked by running this at both commits and comparing the
output (or its digests), on the CPU:

    JAX_PLATFORMS=cpu PYTHONPATH=src python tools/paged_program_hlo.py \
        [--full] > programs.txt

Each program prints as ``<name> <sha256 of its StableHLO text>``; with
``--full`` the texts follow.  Debug locations are left out, so the text
does not depend on where the code sits in its file.
"""
import argparse
import hashlib

import jax.numpy as jnp
import numpy as np

from repro.core.telemetry import GCConfig
from repro.serve.engine import PagedKVEngine


def programs(e: PagedKVEngine) -> dict:
    ids = jnp.arange(4, dtype=jnp.int32)
    kv = jnp.ones((4, 1, 4), jnp.float32)
    on = jnp.ones((4,), bool)
    st, i32 = e.st, np.int32(1)
    return {
        "jit_pool_append": (e._append, (st, e._freed, e._first, ids, kv,
                                        kv, on)),
        "jit_pool_fork": (e._fork, (st, e._freed, e._first, ids, ids[::-1],
                                    on)),
        "jit_pool_reset": (e._reset, (st, e._freed, e._first, ids, on)),
        "jit_pool_live": (e._live, (st,)),
        "jit_gc_reclaim": (e._reclaim, (st, e._freed, e._first, i32)),
        "jit_gc_evict": (e._evict, (st, e._freed, i32)),
        "jit_snapshot_read": (e._read, (st._replace(k_pages=None,
                                                    v_pages=None), ids, i32)),
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--full", action="store_true",
                    help="print each program's text after the digests")
    args = ap.parse_args()
    e = PagedKVEngine(4, 8, 2, 4, 1, 4, gc=GCConfig(
        policy="slrt", versions_per_slot=2, reader_lanes=2))
    texts = {name: prog.lower(*a).as_text(debug_info=False)
             for name, (prog, a) in programs(e).items()}
    for name, text in texts.items():
        print(name, hashlib.sha256(text.encode()).hexdigest())
    if args.full:
        for name, text in texts.items():
            print(f"\n// {name}\n{text}")


if __name__ == "__main__":
    main()
