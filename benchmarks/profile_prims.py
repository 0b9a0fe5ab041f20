"""Time individual engine primitives on the device at bench shape.

Uses slope-based timing (benchmarks/timing.py) — per-call overhead cancels
in the slope.
"""

from __future__ import annotations

import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.timing import device_time_ms, scan_op


def main():
    import jax
    import jax.numpy as jnp

    from sentinel_tpu.core.config import EngineConfig
    from sentinel_tpu.ops import tables as T
    from sentinel_tpu.ops import window as W
    from sentinel_tpu.ops import gsketch as GS
    from sentinel_tpu.ops.rank import (
        fast_cumsum,
        grouped_exclusive_cumsum,
        grouped_exclusive_cumsum_small,
    )

    B = 131072
    cfg = EngineConfig(
        max_resources=16384,
        max_nodes=16384,
        max_flow_rules=16384,
        batch_size=B,
        use_mxu_tables=True,
        sketch_stats=True,
    )
    rows = cfg.node_rows
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(1, 10000, B, dtype=np.int32))
    big_ids = jnp.asarray(rng.integers(1, 1 << 20, B, dtype=np.int32))
    deltas5 = jnp.ones((B, W.NUM_EVENTS), jnp.int32)
    vals1 = jnp.ones((B,), jnp.int32)
    fvals = jnp.ones((B,), jnp.float32)

    def bench(name, body, **kw):
        dt = device_time_ms(scan_op(body), **kw)
        print(f"{name:46s} {dt:9.3f} ms")

    print("=== XLA matmul path ===")
    bench(f"histogram 5xint32 -> {rows}", lambda i: T.histogram(cfg, ids + i, deltas5, rows))
    bench(f"histogram 1xint32 -> {rows}", lambda i: T.histogram(cfg, ids + i, vals1, rows))
    table2 = jnp.ones((rows, 2), jnp.int32)
    bench("big_gather 2xint32", lambda i: T.big_gather(cfg, table2, ids + i, rows, max_int=1 << 24))
    tslots = jnp.ones((cfg.max_resources + 1, 4), jnp.int32)
    bench("big_gather 4 slots", lambda i: T.big_gather(cfg, tslots, ids + i, cfg.max_resources + 1, max_int=cfg.max_flow_rules))
    packed = jnp.ones((cfg.max_flow_rules + 1, 13), jnp.float32)
    bench("small_gather_fields 13f", lambda i: T.small_gather_fields(cfg, packed, ids + i))
    itab = jnp.ones((cfg.max_flow_rules + 1,), jnp.int32)
    bench("small_gather_int 1 col", lambda i: T.small_gather_int(cfg, itab, ids + i))
    stab = jnp.zeros((cfg.max_flow_rules + 1,), jnp.float32)
    bench("small_scatter_add f32", lambda i: T.small_scatter_add(cfg, stab, ids + i, fvals))
    ks = rows + cfg.max_flow_rules + 1
    bench(f"rank_small 3v S={ks}", lambda i: grouped_exclusive_cumsum_small(ids + i, [fvals, fvals, fvals], ids > 0, ks)[0])
    bench(f"rank_small 1v S={ks}", lambda i: grouped_exclusive_cumsum_small(ids + i, [fvals], ids > 0, ks)[0])
    bench("rank_sort 1v (param)", lambda i: grouped_exclusive_cumsum(big_ids + i, [fvals], ids > 0)[0], k1=16, k2=80)
    st = GS.init_sketch(GS.SketchConfig(2, 500, cfg.sketch_depth, cfg.sketch_width))
    vals3 = jnp.ones((B, 3), jnp.int32)
    bench(f"gsketch add 3p d={cfg.sketch_depth} w={cfg.sketch_width}",
          lambda i: GS.add(st, jnp.int32(100), big_ids + i, vals3, (0, 2, 5), ids > 0,
                           GS.SketchConfig(2, 500, cfg.sketch_depth, cfg.sketch_width)).counts)
    ws = W.init_window(rows, W.WindowConfig(2, 500))
    hist = jnp.ones((rows, W.NUM_EVENTS), jnp.int32)
    rt_hist = jnp.ones((rows,), jnp.float32)
    bench("window add_dense", lambda i: W.add_dense(ws, jnp.int32(100), hist, rt_hist, W.WindowConfig(2, 500)).counts)
    bench("fast_cumsum B", lambda i: fast_cumsum(fvals + i))
    bench("window_event dense", lambda i: W.window_event(ws, jnp.int32(100) + i, W.WindowConfig(2, 500), W.EV_PASS))

if __name__ == "__main__":
    main()
