"""BASELINE benchmark suite: configs #1-#5 (BASELINE.md) + the simulated
cluster harness (SURVEY §4).

Each config prints ONE JSON line; `--all` runs every config and also
writes benchmarks/RESULTS_r3.json.  Config #2 (10k ruled resources,
full-feature engine tick) is the repo-root bench.py headline and is not
duplicated here.

  #1  sentinel-demo-basic parity: resource 'HelloWorld' pinned to 20
      pass/s under ~19k QPS offered load, through the HOST client path
      (reference: README.md:104-116, single JVM).  Virtual time makes the
      enforcement assertion exact.
  #3  parameter flow: 1M distinct hot-param values through the hashed-row
      param store on one ruled resource (reference envelope:
      ParameterMetric.java:38-39 caps at 200k LRU keys per rule).
  #4  degrade: 100k resources with slow-ratio circuit breakers, slow
      completions tripping half of them (reference envelope: 6,000
      resource cap, Constants.java:37).
  #5  simulated cluster: 4096 client nodes hammering one token server
      over the length-prefixed TCP protocol (reference floor:
      ServerFlowConfig.java:31 default 30,000 QPS/namespace).

Host-path configs (#1, #5) force the CPU engine backend.  Engine-path
configs (#3, #4) use the TPU when available.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _force_cpu():
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    jax.config.update("jax_platforms", "cpu")


# ---------------------------------------------------------------------------
# config #1 — demo-basic parity through the host client
# ---------------------------------------------------------------------------


def bench_demo_basic() -> dict:
    _force_cpu()
    import sentinel_tpu as st
    from sentinel_tpu.core.config import EngineConfig
    from sentinel_tpu.runtime.client import SentinelClient
    from sentinel_tpu.utils.time_source import VirtualTimeSource

    vt = VirtualTimeSource()
    cfg = EngineConfig(
        max_resources=64, max_nodes=128, max_flow_rules=64, max_degrade_rules=8,
        max_param_rules=8, batch_size=2048, complete_batch_size=2048,
        enable_minute_window=False,
    )
    client = SentinelClient(cfg=cfg, time_source=vt)
    client.start()
    client.flow_rules.load([st.FlowRule(resource="HelloWorld", count=20)])

    # ~19k QPS offered over 5 virtual seconds in 1900-entry bursts
    offered = passed = 0
    t0 = time.perf_counter()
    for sec in range(5):
        for burst in range(10):
            res = client.check_batch(["HelloWorld"] * 1900)
            offered += 1900
            passed += sum(1 for v, _ in res if v == 0)
            vt.advance(100)
    wall = time.perf_counter() - t0
    client.stop()
    pass_rate = passed / 5.0
    return {
        "metric": "demo_basic_enforced_pass_per_sec",
        "value": round(pass_rate, 2),
        "unit": "pass/s",
        "vs_baseline": round(pass_rate / 20.0, 4),  # reference pins 20
        "offered_qps": offered / 5,
        "host_decisions_per_sec": round(offered / wall),
        "engine_backend": "cpu",
        "host_cores": os.cpu_count(),
        "config": "#1 demo-basic (FlowRule count=20 @ ~19k QPS offered)",
    }


# ---------------------------------------------------------------------------
# config #3 — 1M hot-param keys
# ---------------------------------------------------------------------------


def bench_param_1m() -> dict:
    import jax
    import jax.numpy as jnp

    from sentinel_tpu.core.config import EngineConfig
    from sentinel_tpu.core.rules import ParamFlowRule, FlowRule
    from sentinel_tpu.ops import engine as E
    from sentinel_tpu.runtime.registry import Registry

    platform = jax.devices()[0].platform
    on_tpu = platform != "cpu"
    B = (1 << 17) if on_tpu else (1 << 12)
    cfg = EngineConfig(
        max_resources=1024, max_nodes=1024, max_flow_rules=1024,
        max_param_rules=64, param_width=1 << 16, param_depth=2,
        flow_rules_per_resource=1, param_rules_per_resource=1,
        batch_size=B, complete_batch_size=B,
        enable_minute_window=False, use_mxu_tables=on_tpu,
    )
    reg = Registry(cfg)
    reg.resource_id("api")  # id 1
    ruleset = E.compile_ruleset(
        cfg, reg,
        flow_rules=[FlowRule(resource="api", count=1e9)],
        param_rules=[ParamFlowRule(resource="api", param_idx=0, count=50.0)],
    )
    rng = np.random.default_rng(0)
    n_keys = 1 << 20
    acqs, comps = [], []
    for i in range(8):
        ph0 = rng.zipf(1.2, B).astype(np.int64) % n_keys + 1
        ph = np.stack([ph0.astype(np.int32), np.zeros(B, np.int32)], axis=1)
        acqs.append(
            E.empty_acquire(cfg)._replace(
                res=jnp.full((B,), 1, jnp.int32),
                count=jnp.ones((B,), jnp.int32),
                param_hash=jnp.asarray(ph),
            )
        )
        comps.append(E.empty_complete(cfg))
    tick = E.make_tick(cfg, donate=True, features=frozenset({"param", "flow"}))
    state = E.init_state(cfg)
    z = jnp.float32(0.0)
    for w in range(3):
        state, out = tick(state, ruleset, acqs[w % 8], comps[w % 8], jnp.int32(w), z, z)
    _ = float(out.verdict[0])
    n_ticks = 120 if on_tpu else 20
    t0 = time.perf_counter()
    blocked = 0
    for t in range(n_ticks):
        state, out = tick(state, ruleset, acqs[t % 8], comps[t % 8],
                          jnp.int32(1000 + t * 7), z, z)
    blocked = int((np.asarray(out.verdict) != 0).sum())
    dt = time.perf_counter() - t0
    dps = n_ticks * B / dt
    return {
        "metric": "param_flow_decisions_per_sec@1M_keys",
        "value": round(dps),
        "unit": "decisions/s",
        "vs_baseline": round(n_keys / 200000, 2),  # key capacity vs reference LRU cap
        "distinct_keys": n_keys,
        "blocked_in_last_tick": blocked,
        "batch": B,
        "platform": platform,
        "config": "#3 param flow (1M hot-param values, CMS rows + per-value budgets)",
    }


# ---------------------------------------------------------------------------
# config #4 — 100k resources slow-ratio circuit breaking
# ---------------------------------------------------------------------------


def bench_degrade_100k() -> dict:
    import jax
    import jax.numpy as jnp

    from sentinel_tpu.core.config import EngineConfig
    from sentinel_tpu.core.rules import DegradeRule
    from sentinel_tpu.ops import engine as E
    from sentinel_tpu.ops import degrade as D
    from sentinel_tpu.runtime.registry import Registry

    platform = jax.devices()[0].platform
    on_tpu = platform != "cpu"
    n_res = 100_000 if on_tpu else 2_000
    B = (1 << 17) if on_tpu else (1 << 12)
    cfg = EngineConfig(
        max_resources=1 << 17, max_nodes=1 << 17,
        max_flow_rules=8, max_degrade_rules=1 << 17,
        flow_rules_per_resource=1, degrade_rules_per_resource=1,
        batch_size=B, complete_batch_size=B,
        enable_minute_window=False, use_mxu_tables=on_tpu,
    )
    reg = Registry(cfg)
    rules = []
    for i in range(n_res):
        name = f"svc-{i}"
        reg.resource_id(name)
        rules.append(
            DegradeRule(resource=name, grade=0, count=50.0, time_window=5,
                        min_request_amount=5, slow_ratio_threshold=0.5)
        )
    ruleset = E.compile_ruleset(cfg, reg, degrade_rules=rules)
    rng = np.random.default_rng(0)
    acqs, comps = [], []
    for i in range(8):
        ids = jnp.asarray(rng.integers(1, n_res + 1, B, dtype=np.int32))
        # resources with even id complete slow -> their breakers should trip
        slow = (np.asarray(ids) % 2) == 0
        rt = np.where(slow, 120.0, 3.0).astype(np.float32)
        acqs.append(
            E.empty_acquire(cfg)._replace(res=ids, count=jnp.ones((B,), jnp.int32))
        )
        comps.append(
            E.empty_complete(cfg)._replace(
                res=ids, rt=jnp.asarray(rt), success=jnp.ones((B,), jnp.int32)
            )
        )
    tick = E.make_tick(cfg, donate=True, features=frozenset({"degrade"}))
    state = E.init_state(cfg)
    z = jnp.float32(0.0)
    for w in range(3):
        state, out = tick(state, ruleset, acqs[w % 8], comps[w % 8], jnp.int32(w), z, z)
    _ = float(out.verdict[0])
    n_ticks = 120 if on_tpu else 20
    t0 = time.perf_counter()
    for t in range(n_ticks):
        state, out = tick(state, ruleset, acqs[t % 8], comps[t % 8],
                          jnp.int32(1000 + t * 7), z, z)
    blocked = int((np.asarray(out.verdict) != 0).sum())
    dt = time.perf_counter() - t0
    open_cbs = int((np.asarray(state.cb_state) == D.CB_OPEN).sum())
    dps = n_ticks * B / dt
    return {
        "metric": "degrade_decisions_per_sec@100k_breakers",
        "value": round(dps),
        "unit": "decisions/s",
        "vs_baseline": round(n_res / 6000, 2),  # breaker capacity vs 6k chain cap
        "resources": n_res,
        "open_breakers": open_cbs,
        "blocked_in_last_tick": blocked,
        "batch": B,
        "platform": platform,
        "config": "#4 slow-ratio circuit breaking (100k resources)",
    }


# ---------------------------------------------------------------------------
# config #5 — simulated 4096-node cluster over the TCP token protocol
# ---------------------------------------------------------------------------


def bench_cluster_4096(n_nodes: int = 4096, duration_s: float = 8.0, native_front: bool = False, procs: int = 1, shards: int = 1) -> dict:
    _force_cpu()
    import asyncio
    import struct
    import threading

    from sentinel_tpu.cluster import constants as C
    from sentinel_tpu.cluster import protocol as P
    from sentinel_tpu.cluster.rules import ServerFlowConfig
    from sentinel_tpu.cluster.server import ClusterTokenServer
    from sentinel_tpu.cluster.token_service import DefaultTokenService
    from sentinel_tpu.core.config import EngineConfig
    from sentinel_tpu.core.rules import FlowRule
    from sentinel_tpu.runtime.client import SentinelClient

    ns = "bench-ns"
    flow_id = 101
    cfg = EngineConfig(
        max_resources=256, max_nodes=512, max_flow_rules=256, max_degrade_rules=8,
        max_param_rules=8, batch_size=8192, complete_batch_size=8192,
        enable_minute_window=False,
    )
    decision = SentinelClient(cfg=cfg, mode="threaded", tick_interval_ms=2.0)
    decision.start()
    svc = DefaultTokenService(decision)
    # lift the per-namespace guard (ServerFlowConfig default 30k QPS is the
    # reference FLOOR this harness is trying to beat)
    svc.config.set_flow_config(ns, ServerFlowConfig(max_allowed_qps=10_000_000.0))
    svc.flow_rules.load(
        ns,
        [
            FlowRule(
                resource=f"res-{flow_id}", count=1e9, cluster_mode=True,
                cluster_flow_id=flow_id,
            )
        ],
    )
    door = None
    doors = []
    if native_front:
        from sentinel_tpu.cluster.front_door import NativeFrontDoor

        # SO_REUSEPORT sharding: N io threads on one port (the multi-core
        # scaling axis; on a 1-core host the curve documents the ceiling)
        doors = [NativeFrontDoor(port=0, reuseport=shards > 1)]
        for _ in range(shards - 1):
            doors.append(NativeFrontDoor(port=doors[0].port, reuseport=True))
        for d in doors:
            d.follow(svc)
            decision.attach_front_door(d)
            d.start()
        door = doors[0]
        port = door.port
        server = None
    else:
        server = ClusterTokenServer(svc, host="127.0.0.1", port=0, workers=64)
        server.start()
        port = server.port

    if procs > 1:
        # client load in separate processes: a single Python loop saturates
        # near ~10k msg/s and would measure the CLIENT, not the server
        import subprocess as sp

        per = max(n_nodes // procs, 1)
        t0 = time.perf_counter()
        children = [
            sp.Popen(
                [sys.executable, os.path.abspath(__file__), "_client5",
                 "--port", str(port), "--nodes", str(per),
                 "--duration", str(duration_s)],
                stdout=sp.PIPE, text=True,
                env={**os.environ, "JAX_PLATFORMS": "cpu"},
            )
            for _ in range(procs)
        ]
        agg = {"ok": 0, "blocked": 0, "other": 0}
        active = duration_s
        for ch in children:
            out, _ = ch.communicate(timeout=duration_s + 120)
            try:
                d = json.loads(out.strip().splitlines()[-1])
                for k in agg:
                    agg[k] += d.get(k, 0)
                active = max(active, d.get("active_s", duration_s))
            except Exception:
                agg["other"] += 1
        wall = active  # interpreter/jax startup excluded
        if server is not None:
            server.stop()
        for d in doors:
            d.stop()
        decision.stop()
        for d in doors:
            d.close()
        total = sum(agg.values())
        qps = total / wall if wall > 0 else 0.0
        return {
            "metric": "cluster_token_qps@4096_nodes",
            "value": round(qps),
            "unit": "tokens/s",
            "vs_baseline": round(qps / 30000, 4),
            "nodes": n_nodes,
            "client_procs": procs,
            "granted": agg["ok"],
            "blocked": agg["blocked"],
            "errors": agg["other"],
            "engine_backend": "cpu",
            "front_door": "native-epoll" if native_front else "asyncio",
            "io_shards": shards if native_front else 1,
            "config": "#5 simulated cluster (4096 TCP nodes -> one token server)",
        }

    stats = {"ok": 0, "blocked": 0, "other": 0}
    stop_at = time.perf_counter() + duration_s

    async def read_frame(reader):
        head = await reader.readexactly(2)
        (n,) = struct.unpack(">H", head)
        return await reader.readexactly(n)

    async def node(idx: int):
        try:
            reader, writer = await asyncio.open_connection("127.0.0.1", port)
        except OSError:
            stats["other"] += 1
            return
        try:
            # announce namespace (PING carries it, like the reference client)
            writer.write(
                P.encode_request(
                    P.ClusterRequest(xid=0, type=C.MSG_TYPE_PING, namespace=ns)
                )
            )
            await writer.drain()
            await read_frame(reader)
            xid = 1
            while time.perf_counter() < stop_at:
                writer.write(
                    P.encode_request(
                        P.ClusterRequest(
                            xid=xid, type=C.MSG_TYPE_FLOW, flow_id=flow_id, count=1
                        )
                    )
                )
                await writer.drain()
                resp = P.decode_response(await read_frame(reader))
                if resp.status == C.STATUS_OK:
                    stats["ok"] += 1
                elif resp.status == C.STATUS_BLOCKED:
                    stats["blocked"] += 1
                else:
                    stats["other"] += 1
                xid += 1
        except (OSError, asyncio.IncompleteReadError, ConnectionError):
            pass
        finally:
            try:
                writer.close()
            except Exception:
                pass

    async def run_all():
        await asyncio.gather(*(node(i) for i in range(n_nodes)))

    t0 = time.perf_counter()
    loop = asyncio.new_event_loop()
    t = threading.Thread(target=lambda: loop.run_until_complete(run_all()), daemon=True)
    t.start()
    t.join(timeout=duration_s + 120)
    wall = time.perf_counter() - t0
    if server is not None:
        server.stop()
    if door is not None:
        door.stop()
    decision.stop()
    if door is not None:
        door.close()
    total = stats["ok"] + stats["blocked"] + stats["other"]
    qps = total / wall if wall > 0 else 0.0
    return {
        "metric": "cluster_token_qps@4096_nodes",
        "value": round(qps),
        "unit": "tokens/s",
        "vs_baseline": round(qps / 30000, 4),  # ServerFlowConfig default cap
        "nodes": n_nodes,
        "granted": stats["ok"],
        "blocked": stats["blocked"],
        "errors": stats["other"],
        "engine_backend": "cpu",
        "host_cores": os.cpu_count(),
        "front_door": "native-epoll" if native_front else "asyncio",
        "config": "#5 simulated cluster (4096 TCP nodes -> one token server)",
    }


# ---------------------------------------------------------------------------


BENCHES = {
    "1": bench_demo_basic,
    "3": bench_param_1m,
    "4": bench_degrade_100k,
    "5": bench_cluster_4096,
}


def _client5(port: int, n_nodes: int, duration_s: float) -> None:
    """Client-side worker for config #5 multi-process mode: n_nodes
    connections against an already-running token server; prints counts."""
    import asyncio
    import struct

    from sentinel_tpu.cluster import constants as C
    from sentinel_tpu.cluster import protocol as P

    stats = {"ok": 0, "blocked": 0, "other": 0}
    stop_at = time.perf_counter() + duration_s  # starts after imports
    flow_id = 101
    ns = "bench-ns"

    async def read_frame(reader):
        head = await reader.readexactly(2)
        (n,) = struct.unpack(">H", head)
        return await reader.readexactly(n)

    async def node(idx):
        try:
            reader, writer = await asyncio.open_connection("127.0.0.1", port)
        except OSError:
            stats["other"] += 1
            return
        try:
            writer.write(P.encode_request(P.ClusterRequest(xid=0, type=C.MSG_TYPE_PING, namespace=ns)))
            await writer.drain()
            await read_frame(reader)
            xid = 1
            while time.perf_counter() < stop_at:
                writer.write(P.encode_request(P.ClusterRequest(
                    xid=xid, type=C.MSG_TYPE_FLOW, flow_id=flow_id, count=1)))
                await writer.drain()
                resp = P.decode_response(await read_frame(reader))
                if resp.status == C.STATUS_OK:
                    stats["ok"] += 1
                elif resp.status == C.STATUS_BLOCKED:
                    stats["blocked"] += 1
                else:
                    stats["other"] += 1
                xid += 1
        except (OSError, asyncio.IncompleteReadError, ConnectionError):
            pass
        finally:
            try:
                writer.close()
            except Exception:
                pass

    async def _all():
        await asyncio.gather(*(node(i) for i in range(n_nodes)))

    t0 = time.perf_counter()
    asyncio.run(_all())
    stats["active_s"] = round(time.perf_counter() - t0, 3)
    print(json.dumps(stats))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("config", nargs="?", default="all", help="1|3|4|5|all|_client5")
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument("--procs", type=int, default=1)
    ap.add_argument("--nodes", type=int, default=4096)
    ap.add_argument("--duration", type=float, default=8.0)
    ap.add_argument("--native-front", action="store_true",
                    help="config #5: native epoll front door instead of asyncio")
    ap.add_argument("--shards", type=int, default=1,
                    help="config #5: SO_REUSEPORT io shards for the native door")
    args = ap.parse_args()
    if args.config == "_client5":
        _client5(args.port, args.nodes, args.duration)
        return
    if args.config == "all":
        # each config in its own process: #1/#5 force the CPU backend with a
        # process-global jax config flip that must not leak into #3/#4
        import subprocess as sp

        results = []
        for k in BENCHES:
            cmd = [sys.executable, os.path.abspath(__file__), k,
                   "--nodes", str(args.nodes), "--duration", str(args.duration),
                   "--procs", str(args.procs)]
            if args.native_front:
                cmd.append("--native-front")
            out = sp.run(cmd, capture_output=True, text=True, timeout=1800)
            for line in out.stdout.strip().splitlines():
                try:
                    r = json.loads(line)
                except Exception:
                    continue
                print(json.dumps(r), flush=True)
                results.append(r)
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "RESULTS_r3.json")
        with open(path, "w") as f:
            json.dump(results, f, indent=1)
        return

    k = args.config
    fn = BENCHES[k]
    if k == "5":
        r = fn(n_nodes=args.nodes, duration_s=args.duration,
               native_front=args.native_front, procs=args.procs, shards=args.shards)
    else:
        r = fn()
    print(json.dumps(r), flush=True)


if __name__ == "__main__":
    main()
