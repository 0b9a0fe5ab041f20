"""One ``SentinelClient`` on the first chip under hot-parameter rules only,
as an API gateway behind Sentinel's adapter has it: every route carries one
``ParamFlowRule`` on its client parameter (``GatewayRuleConverter`` makes
every gateway rule a ``ParamFlowRule``), with one exception item on the
route's most frequent client.  The traffic pool's hash lane carries
``hash_param(client)``, as ``entry(args=(client,))`` would.

The client, its entry points, the wire, the tick's shapes and the kernels are
``single_client``'s; what is this kind's own is the rule set, a pool whose
values repeat (Zipf over a route's clients), and the key tables the check
needs.  Tables, rules and the pool are made here from the seed.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
from typing import Dict, List, Optional, Tuple

import numpy as np

from perfbench.deployments import with_sizes
from perfbench.deployments.single_client import (  # noqa: F401 (read by the harness)
    HOST_SPANS, TICK_SPAN, Columns, host_intervals, journal,
)

#: added to every count while ``control()`` holds
_COUNT_OFF = 0


@dataclasses.dataclass
class Deployment:
    client: object
    config: dict
    pool: List[Columns]
    route_ids: np.ndarray  # engine id of every route, in rule order
    client_hashes: np.ndarray  # hash_param of every client value
    item_client: np.ndarray  # per route: index of the client its exception item names
    batch: int
    universe: int  # (route, client) pairs that can occur
    pool_pairs: int  # distinct pairs the pool holds
    _serving: bool = False

    def start(self) -> None:
        self.client.start()  # rules are loaded: starting first would compile twice
        self._serving = True

    def stop(self) -> None:
        """Stops the tick thread; the client still answers ``tick_once``."""
        if self._serving:
            self._serving = False
            self.client.stop()

    def thresholds(self) -> Tuple[Dict[int, float], Dict[int, float]]:
        """What the configuration states, for the plain reference: admissions
        a window per route id, and per (route id << 32 | value hash) of every
        exception item."""
        r = self.config["rules"]
        window_s = r["duration_in_sec"]
        rule = {int(i): float(r["count"] * window_s + r["burst_count"]) for i in self.route_ids}
        item = {(int(i) << 32) | int(self.client_hashes[c]): float(r["item_count"] * window_s)
                for i, c in zip(self.route_ids, self.item_client)}
        return rule, item


def zipf_cdf(n: int, a: float) -> np.ndarray:
    """Cumulative shares of ranks 1..n under p(k) ~ k**-a (bounded: no tail
    folded back, and exponents under 1 are fine)."""
    p = np.arange(1, n + 1, dtype=np.float64) ** -a
    return np.cumsum(p / p.sum())


def client_value(j: int) -> str:
    return f"client-{j:04d}"


def item_clients(n_routes: int, n_clients: int, stride: int) -> np.ndarray:
    """The most frequent client of every route: rank 1 of the route's own
    order, which is the common order rotated by ``stride`` a route, so that
    no client is the hottest everywhere."""
    return (np.arange(n_routes, dtype=np.int64) * stride) % n_clients


def _rules(c, cfg: dict, routes: List[str], item_client: np.ndarray) -> None:
    from sentinel_tpu.core.rules import ParamFlowItem, ParamFlowRule

    r = cfg["rules"]
    c.param_flow_rules.load([
        ParamFlowRule(
            resource=name, param_idx=0, count=r["count"] + _COUNT_OFF,
            duration_in_sec=r["duration_in_sec"], burst_count=r["burst_count"],
            param_flow_item_list=[
                ParamFlowItem(object=client_value(int(j)), count=r["item_count"] + _COUNT_OFF)],
        )
        for name, j in zip(routes, item_client)
    ])


@contextlib.contextmanager
def control():
    """The control of this kind's cells (``study.py control``): while this
    holds, ``build`` loads every count, the rules' and the items', one higher
    than its configuration states, so "a key never admits more than its
    threshold" is broken and a run has to come out as not correct."""
    global _COUNT_OFF
    _COUNT_OFF = 1
    try:
        yield
    finally:
        _COUNT_OFF = 0


def make_pool(cfg: dict, seed: int, batch: int, route_ids: np.ndarray, client_hashes: np.ndarray,
              trash_row: int, param_dims: int) -> List[Columns]:
    """``pool_batches`` full batches: a route by Zipf over the routes, a
    client by Zipf over that route's clients, every item carrying its
    client's hash in lane 0."""
    res, tr = cfg["resources"], cfg["traffic"]
    n_routes, n_clients = res["n_routes"], res["clients_per_route"]
    route_cdf = zipf_cdf(n_routes, tr["route_zipf_a"])
    client_cdf = zipf_cdf(n_clients, tr["client_zipf_a"])
    rng = np.random.default_rng(seed)
    no_origin = np.full(batch, trash_row, np.int32)
    no_origin_id = np.full(batch, -1, np.int32)
    pool = []
    for _ in range(tr["pool_batches"]):
        route = np.minimum(np.searchsorted(route_cdf, rng.random(batch)), n_routes - 1)
        rank = np.minimum(np.searchsorted(client_cdf, rng.random(batch)), n_clients - 1)
        client = (rank + route * tr["client_rotation"]) % n_clients
        ph = np.zeros((batch, param_dims), np.int32)
        ph[:, 0] = client_hashes[client]
        inb = (rng.random(batch) < tr["inbound_share"]).astype(np.int32)
        rt = np.abs(rng.normal(tr["rt_ms_mean"], tr["rt_ms_sd"], batch)).astype(np.float32)
        pool.append((route_ids[route].astype(np.int32), no_origin, no_origin_id, ph, inb, rt))
    return pool


def build(cfg: dict, seed: int, sizes: Optional[dict] = None) -> Deployment:
    """The configuration's client (not started), its rules and its pool."""
    from sentinel_tpu.core.config import platform_engine_config
    from sentinel_tpu.core.rule_tensors import hash_param
    from sentinel_tpu.ops import param as store
    from sentinel_tpu.runtime.client import SentinelClient

    cfg = with_sizes(cfg, sizes)
    res = cfg["resources"]
    if not hasattr(store, "wide") and cfg["engine"]["param_width"] > 1 << 14:
        # a program from before the wide store keeps a fused job's whole
        # table in fast memory: refuse here, in one line, not in the compiler
        raise RuntimeError(
            f"this program's hot-parameter store has no wide form and cannot hold "
            f"param_width {cfg['engine']['param_width']}")
    c = SentinelClient(cfg=platform_engine_config(**cfg["engine"]), **cfg["client"])

    routes = [f"route-{k + 1}" for k in range(res["n_routes"])]
    route_ids = np.array([c.registry.resource_id(n) for n in routes], np.int64)
    if (route_ids > c.cfg.max_resources).any():
        raise RuntimeError("a route got no exact row: ParamFlowRules need one")
    n_clients = res["clients_per_route"]
    client_hashes = np.array([hash_param(client_value(j)) for j in range(n_clients)], np.int64)
    if len(np.unique(client_hashes)) != n_clients:
        raise RuntimeError("two client values share a hash: the exact shadow could not tell them apart")
    item_client = item_clients(len(routes), n_clients, cfg["traffic"]["client_rotation"])
    _rules(c, cfg, routes, item_client)
    pool = make_pool(cfg, seed, c.cfg.batch_size, route_ids, client_hashes,
                     c.cfg.trash_row, c.cfg.param_dims)
    pairs = len(np.unique(np.concatenate(
        [(b[0].astype(np.int64) << 32) | b[3][:, 0] for b in pool])))
    universe = len(routes) * n_clients
    print(json.dumps({"deployment": "param_client", "universe_pairs": universe, "pool_pairs": pairs,
                      "pool_items": len(pool) * c.cfg.batch_size,
                      "param_width": c.cfg.param_width, "param_depth": c.cfg.param_depth}),
          flush=True)
    return Deployment(c, cfg, pool, route_ids, client_hashes, item_client, c.cfg.batch_size,
                      universe, pairs)
