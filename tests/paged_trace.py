"""The random op trace the paged engines' loop tests drive, and the small
geometry it is drawn for: 4 sequences of at most 4 pages of 2 tokens, a
pool of 8 pages (2 versions per slab, set by each test's `GCConfig`), so
that appends overflow the slabs and the pool, the watermark is crossed
and lanes give up after every reclaim round."""

B, PAGES, PS, MP, HKV, D = 4, 8, 2, 4, 1, 4


def trace(rng, n_ops):
    """(op, args) pairs; masks, values and lanes drawn from ``rng``."""
    ops = []
    for i in range(n_ops):
        op = rng.choice(["step"] * 10 + ["reset", "fork"] * 2 + ["pin",
                        "unpin", "reclaim"] + ["arm"] * (i > n_ops // 2))
        if op == "step":
            ops.append((op, (rng.random(B) < 0.8, float(i + 1))))
        elif op == "reset":
            ops.append((op, (rng.random(B) < 0.4,)))
        elif op == "fork":
            src, dst = rng.choice(B, size=2, replace=False)
            ops.append((op, (int(src), int(dst))))
        elif op in ("pin", "unpin"):
            ops.append((op, (int(rng.integers(2)),)))
        elif op == "reclaim":
            ops.append((op, (None if rng.random() < 0.5
                             else int(rng.integers(1, 2 * PAGES)),)))
        else:
            ops.append((op, ()))
    return ops
