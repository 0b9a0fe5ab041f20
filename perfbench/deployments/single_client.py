"""One ``SentinelClient`` on the first chip, built through the public client
surface: ``res-<i>`` names with a rule set each and a pool of Zipf traffic.

A copy of ``bench.served_scenario`` (sound; see PERF.md section 6) that reads
its sizes from ``perfbench/configs/<name>.json`` and also serves a
configuration without tail rules.  Tables, rules and the traffic pool are made
here from the seed; nothing is loaded from disk.
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
from typing import List, Optional, Tuple

import numpy as np

from perfbench.deployments import intervals, with_sizes

#: one batch of the pool: ids, origin_node, origin_id, param_hash, inbound, rt
Columns = Tuple[np.ndarray, ...]
#: the span ``runtime/client.py`` opens once a tick, on the tick thread
TICK_SPAN = "tick.assemble"
#: its host spans that can explain an idle device, most specific first: the
#: five of a tick's path, then those that tile the rest of the tick thread.
#: ``tick.device``, ``tick.resident`` and ``tick.wait`` are waits for the
#: device itself and explain nothing
HOST_SPANS = ("tick.presort", TICK_SPAN, "tick.dispatch", "tick.readback", "tick.resolve",
              "tick.drain", "tick.handoff", "tick.hotset", "tick.lock", "tick.idle")


def host_intervals(spans: List[dict]) -> list:
    """``HOST_SPANS`` as the intervals they cover.  ``tick.assemble`` is
    recorded with its own duration, the presort inside it apart: the two
    together run from assemble's start to its tick's dispatch, so assemble's
    interval is lengthened by its tick's presort (what then lies under both
    goes to the presort, which is asked first)."""
    presort = {s["trace"]: s["dur_ns"] for s in spans if s["name"] == "tick.presort"}
    return [intervals(spans, n, presort if n == TICK_SPAN else None) for n in HOST_SPANS]


def journal(t0_ns: int, t1_ns: int) -> list:
    """The program's flight journal (``obs.FLIGHT``: rare state changes such
    as a rule recompile, a hot-set promotion, a capacity resize, a watchdog
    that fired, a tick failed closed) between two instants of
    ``monotonic_ns``, as ``(t_ns, kind, fields)``; its newest 64 at most."""
    from sentinel_tpu import obs

    return [(e["t_ns"], e["kind"], e["fields"]) for e in obs.FLIGHT.events(last=64)
            if t0_ns <= e["t_ns"] < t1_ns]


@dataclasses.dataclass
class Deployment:
    client: object
    config: dict
    pool: List[Columns]
    ruled_names: List[str]
    tail_ids: np.ndarray  # current engine id of every tail-ruled resource
    batch: int
    sketch_base: int  # engine ids from here on live in the sketch tier
    _serving: bool = False

    def start(self) -> None:
        self.client.start()  # rules are loaded: starting first would compile twice
        self._serving = True

    def stop(self) -> None:
        """Stops the tick thread; the client still answers ``tick_once``."""
        if self._serving:
            self._serving = False
            self.client.stop()


def _rules(c, cfg: dict, ruled: List[str], tail_names: List[str]) -> None:
    from sentinel_tpu.core.rules import (
        AUTHORITY_BLACK,
        AuthorityRule,
        DegradeRule,
        FlowRule,
        ParamFlowRule,
        SystemRule,
    )

    r = cfg["rules"]
    c.flow_rules.load(
        [FlowRule(resource=n, count=r["flow_qps"]) for n in ruled]
        + [FlowRule(resource=n, count=r["tail_qps"]) for n in tail_names]
    )
    if r["degrade"]:
        c.degrade_rules.load([DegradeRule(resource=n, **r["degrade"]) for n in ruled])
    if r["n_param_ruled"]:
        c.param_flow_rules.load(
            [
                ParamFlowRule(resource=n, param_idx=0, count=r["param_qps"])
                for n in ruled[: r["n_param_ruled"]]
            ]
        )
    if r["n_authority_ruled"]:
        c.authority_rules.load(
            [
                AuthorityRule(
                    resource=n,
                    limit_app=r["authority_black_app"],
                    strategy=AUTHORITY_BLACK,
                )
                for n in ruled[: r["n_authority_ruled"]]
            ]
        )
    if r["system_qps"]:
        c.system_rules.load([SystemRule(qps=r["system_qps"])])


@contextlib.contextmanager
def control():
    """The control of this kind's cells (``study.py control``): while this
    holds, ``build`` loads every FlowRule one per cent above what its
    configuration states (rounded up), so the guarantee "over-limit blocked"
    is broken and a run has to come out as not correct.  (On the chip one
    more than 1000 was not enough: all twelve such runs of PR 23 came out
    correct, see PERF.md.)"""
    global _rules
    real = _rules

    def one_more(c, cfg, ruled, tail_names):
        rules = dict(cfg["rules"], flow_qps=math.ceil(cfg["rules"]["flow_qps"] * 1.01))
        real(c, dict(cfg, rules=rules), ruled, tail_names)

    _rules = one_more
    try:
        yield
    finally:
        _rules = real


def make_pool(cfg: dict, seed: int, batch: int, tail_ids, node_rows, trash_row,
              origin_row, origin_id, param_dims) -> List[Columns]:
    """``pool_batches`` full batches of seeded Zipf traffic as column tuples."""
    res, tr, r = cfg["resources"], cfg["traffic"], cfg["rules"]
    n_ruled, n_tail, universe = res["n_ruled"], res["n_tail_ruled"], res["id_universe"]
    rng = np.random.default_rng(seed)
    pool = []
    for _ in range(tr["pool_batches"]):
        z = rng.zipf(tr["zipf_a"], size=batch).astype(np.int64)
        raw = (z - 1) % universe + 1
        tail_k = raw - n_ruled - 1  # >= 0 beyond the ruled ids
        if n_tail:
            beyond = np.where(
                tail_k < n_tail,
                tail_ids[np.clip(tail_k, 0, n_tail - 1)],
                node_rows + tail_k,
            )
        else:
            beyond = node_rows + tail_k
        ids = np.where(raw <= n_ruled, raw, beyond).astype(np.int32)
        with_origin = rng.random(batch) < tr["origin_share"]
        onode = np.where(with_origin, origin_row, trash_row).astype(np.int32)
        oid = np.where(with_origin, origin_id, -1).astype(np.int32)
        ph = np.zeros((batch, param_dims), np.int32)
        ph[:, 0] = np.where(
            ids <= r["n_param_ruled"], rng.integers(1, 1 << 20, batch), 0
        )
        inb = (rng.random(batch) < tr["inbound_share"]).astype(np.int32)
        rt = np.abs(rng.normal(tr["rt_ms_mean"], tr["rt_ms_sd"], batch)).astype(
            np.float32
        )
        pool.append((ids, onode, oid, ph, inb, rt))
    return pool


def build(cfg: dict, seed: int, sizes: Optional[dict] = None) -> Deployment:
    """The configuration's client (not started) and its traffic pool."""
    from sentinel_tpu.core.config import platform_engine_config
    from sentinel_tpu.runtime.client import SentinelClient

    cfg = with_sizes(cfg, sizes)
    res = cfg["resources"]
    ecfg = platform_engine_config(**cfg["engine"])
    c = SentinelClient(cfg=ecfg, **cfg["client"])

    ruled = [f"res-{i + 1}" for i in range(res["n_ruled"])]
    for i, name in enumerate(ruled):
        if c.registry.resource_id(name) != i + 1:
            raise RuntimeError(f"{name} did not intern as id {i + 1}")
    tail_names = [f"tail-{k}" for k in range(res["n_tail_ruled"])]
    if res["fill_exact_tier"]:
        # exhaust the organic exact space so later names intern as sketch ids
        while not c.registry.is_sketch_id(
            c.registry.resource_id(f"burn-{c.registry.num_resources}")
        ):
            pass
    for n in tail_names:
        c.registry.resource_id(n)
    _rules(c, cfg, ruled, tail_names)
    # a rule load may promote tail resources into exact rows: traffic follows
    # the registry's current ids
    tail_ids = np.array(
        [c.registry.peek_resource_id(n) for n in tail_names], np.int64
    )
    origin_app = cfg["traffic"]["origin_app"]
    pool = make_pool(
        cfg, seed, c.cfg.batch_size, tail_ids, c.cfg.node_rows, c.cfg.trash_row,
        c.registry.origin_node_row(ruled[0], origin_app),
        c.registry.origin_id(origin_app), c.cfg.param_dims,
    )
    return Deployment(c, cfg, pool, ruled, tail_ids, c.cfg.batch_size, c.cfg.node_rows)
