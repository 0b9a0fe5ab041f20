"""Trace the EXACT bench.py tick on the real chip and print per-op device time.

Runs bench.build() at the honest full-feature shape, scans K ticks under one
jit, captures a jax.profiler trace, and aggregates XLA op device time from
the xplane proto (parsed with tensorboard_plugin_profile, available in this
image).  This is the truth source for where the tick's milliseconds go.

Usage: python benchmarks/profile_bench_trace.py [--batch 131072] [--k 12]
"""

from __future__ import annotations

import argparse
import collections
import glob
import os
import re
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def parse_xplane(logdir: str):
    """Aggregate device-stream op durations from the captured xplane."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"), recursive=True)
    assert paths, f"no xplane in {logdir}"
    agg = collections.Counter()
    total_ps = 0
    n_planes = 0  # guard: >1 device plane would multiply ms/tick
    for path in paths:
        xs = xplane_pb2.XSpace()
        with open(path, "rb") as f:
            xs.ParseFromString(f.read())
        for plane in xs.planes:
            if "TPU" not in plane.name and "/device" not in plane.name.lower():
                continue
            ev_meta = plane.event_metadata
            for line in plane.lines:
                if line.name not in ("XLA Ops",):
                    continue
                if line.events:
                    n_planes += 1
                evs = sorted(
                    (
                        (ev.offset_ps, ev.offset_ps + ev.duration_ps,
                         ev_meta[ev.metadata_id].name)
                        for ev in line.events
                    ),
                    key=lambda t: (t[0], -t[1]),
                )
                # nesting stack -> self time = duration - children
                stack = []  # (start, end, name, child_ps)
                for s, e, name in evs:
                    while stack and stack[-1][1] <= s:
                        st = stack.pop()
                        self_ps = (st[1] - st[0]) - st[3]
                        agg[st[2]] += self_ps
                        total_ps += self_ps
                        if stack:
                            stack[-1][3] += st[1] - st[0]
                    stack.append([s, e, name, 0])
                while stack:
                    st = stack.pop()
                    self_ps = (st[1] - st[0]) - st[3]
                    agg[st[2]] += self_ps
                    total_ps += self_ps
                    if stack:
                        stack[-1][3] += st[1] - st[0]
    if n_planes > 1:
        print(f"WARNING: {n_planes} device op planes aggregated — "
              f"ms/tick below is the SUM across cores, not per-core")
    return agg, total_ps


def bucket(name: str) -> str:
    """Collapse XLA op names into readable buckets."""
    name = name.split(" = ")[0].lstrip("%")
    return re.sub(r"\.\d+$", "", name)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=131072)
    ap.add_argument("--k", type=int, default=12)
    ap.add_argument("--top", type=int, default=45)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    import bench

    cfg, E, ruleset, acqs, comps, seg_info = bench.build(args.batch, True)
    print("segments:", seg_info)
    KS = 4
    sacq = jax.tree.map(lambda *xs: jnp.stack(xs), *(acqs[i % len(acqs)] for i in range(KS)))
    scomp = jax.tree.map(lambda *xs: jnp.stack(xs), *(comps[i % len(comps)] for i in range(KS)))
    state0 = E.init_state(cfg)
    load = jnp.float32(0.0)
    cpu = jnp.float32(0.0)

    @jax.jit
    def many(state, base):
        def body(s, t):
            a = jax.tree.map(lambda x: x[t % KS], sacq)
            c = jax.tree.map(lambda x: x[t % KS], scomp)
            s, o = E.tick(s, ruleset, a, c, base + t * 7, load, cpu,
                          cfg=cfg, features=E.ALL_FEATURES)
            return s, o.verdict[0]

        state, vs = jax.lax.scan(body, state, jnp.arange(args.k, dtype=jnp.int32))
        return state, vs

    jax.block_until_ready(many(state0, jnp.int32(0)))
    t0 = time.perf_counter()
    jax.block_until_ready(many(state0, jnp.int32(7)))
    wall = time.perf_counter() - t0
    print(f"scan of {args.k} ticks wall: {wall*1000:.2f} ms "
          f"({wall*1000/args.k:.3f} ms/tick incl. dispatch)")

    logdir = tempfile.mkdtemp(prefix="sentinel_trace_")
    jax.profiler.start_trace(logdir)
    jax.block_until_ready(many(state0, jnp.int32(13)))
    jax.profiler.stop_trace()

    agg, total_ps = parse_xplane(logdir)
    per_tick_ms = total_ps / 1e9 / args.k
    print(f"device total: {total_ps/1e9:.2f} ms -> {per_tick_ms:.3f} ms/tick over {args.k} ticks")
    groups = collections.Counter()
    for name, ps in agg.items():
        groups[bucket(name)] += ps
    print(f"{'ms/tick':>9}  {'%':>5}  op")
    for name, ps in groups.most_common(args.top):
        ms = ps / 1e9 / args.k
        print(f"{ms:9.4f}  {100.0*ps/total_ps:5.1f}  {name}")


if __name__ == "__main__":
    main()
