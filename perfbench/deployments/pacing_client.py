"""One ``SentinelClient`` on the first chip under uniform-rate rules only, as
a message broker's consumer side or a gateway in front of a fragile backend
has it: every topic carries one ``FlowRule`` whose control behaviour is
RATE_LIMITER (a leaky bucket: ``count`` a second, one item every
``round(1000 / count)`` ms, a queue of ``max_queueing_time_ms``), and nothing
else.  Producers send in batches, so a topic's items come in bursts; an
admitted item is told how long to wait (PASS_WAIT), held that long by its
caller, served, and exits.

The client, its entry points, the wire, the tick's shapes and the kernels are
``single_client``'s, and so are the spans.  What is this kind's own: the rule
set (a seeded count a topic), a pool that is one stream of bursts cut into
batches, a service time an item, and the buckets' ``latestPassedTime`` read
back for the check.  Names, counts, bursts and service times are made here
from the seed.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import math
from typing import List, Optional

import numpy as np

from perfbench.deployments import with_sizes
from perfbench.deployments.breaker_client import whole_ms
from perfbench.deployments.single_client import (  # noqa: F401 (read by the harness)
    HOST_SPANS, TICK_SPAN, Columns, host_intervals, journal,
)
from perfbench.reference.plain_pacer import NEVER  # what the reference calls an idle bucket

#: added to every rule's ``max_queueing_time_ms`` while ``control()`` holds
_QUEUE_OFF_MS = 0


@dataclasses.dataclass
class Deployment:
    client: object
    config: dict
    pool: List[Columns]  # a batch: ids, origin_node, origin_id, param_hash, inbound, service time
    pool_rank: List[np.ndarray]  # per batch: every item's topic, 0-based rank
    ids: np.ndarray  # engine id (= exact row) of the topic of every rank
    counts: np.ndarray  # the rule's count a second, per rank (whole numbers)
    cost_ms: np.ndarray  # round(1000 / count), per rank
    slot: np.ndarray  # the compiled rule's slot in the engine's flow planes, per rank
    batch: int
    _serving: bool = False

    def start(self) -> None:
        self.client.start()  # rules are loaded: starting first would compile twice
        self._serving = True

    def stop(self) -> None:
        """Stops the tick thread; the client still answers ``tick_once``."""
        if self._serving:
            self._serving = False
            self.client.stop()

    def latest_passed(self) -> np.ndarray:
        """Per rank, the rule's ``latestPassedTime`` in engine milliseconds
        as it is now (for a stopped client, between ticks); ``NEVER`` for a
        bucket that has admitted nothing."""
        c = self.client
        with c._engine_lock:
            plane = np.asarray(c._state.latest_passed_ms)
        mine = plane[self.slot].astype(np.int64)
        return np.where(mine <= _idle_ms(), NEVER, mine)

    def reset_buckets(self) -> None:
        """Every bucket idle (a stopped client, between ticks), so that a
        replay and its reference start from a state that is known and not
        read back from the program.  The plane is made as the engine makes
        it, uncommitted: one placed with ``device_put`` would be another
        argument kind to the jitted tick, which then compiles every shape
        again (call A of PR 42 spent 180 s of a run's replay so)."""
        import jax.numpy as jnp

        c = self.client
        with c._engine_lock:
            plane = c._state.latest_passed_ms
            c._state = c._state._replace(
                latest_passed_ms=jnp.full(plane.shape, _idle_ms(), plane.dtype))

    def wait_overflow_ticks(self) -> int:
        """``sentinel_wire_wait_overflow_ticks_total`` of this process: ticks
        that read the whole wait column."""
        from sentinel_tpu.runtime.client import _C_WAIT_OVERFLOW

        return int(_C_WAIT_OVERFLOW.value)


def _idle_ms() -> int:
    """The program's ``latestPassedTime`` of a rule that has admitted nothing.
    A program from before PR 42 has no such name: it keeps the plane in
    float32, which holds no odd millisecond past 2^24 ms of engine time, so
    it cannot give what this configuration's ``guarantees`` state (the check
    replays a stretch past that instant) and ``build`` refuses it at once."""
    try:
        from sentinel_tpu.ops.engine import LATEST_IDLE_MS
    except ImportError:
        raise RuntimeError(
            "this program keeps latestPassedTime in float32 (no ops.engine.LATEST_IDLE_MS): it "
            "paces exactly for 4.66 h of engine time only, and rate-limiter-pacing states more"
        ) from None
    return LATEST_IDLE_MS


def topic_name(k: int) -> str:
    return f"topic-{k}"


def topic_counts(cfg: dict, seed: int, n: int) -> np.ndarray:
    """A whole count a topic, log-uniform on ``[count_lo, count_hi]``."""
    r = cfg["rules"]
    u = np.random.default_rng(seed + 6).uniform(math.log(r["count_lo"]), math.log(r["count_hi"] + 1), n)
    return np.clip(np.floor(np.exp(u)), r["count_lo"], r["count_hi"]).astype(np.int64)


def costs_of(counts: np.ndarray) -> np.ndarray:
    """``Math.round(1000 / count)``, which rounds half up, in whole ms."""
    return np.floor(1000.0 / counts + 0.5).astype(np.int64)


def _rules(c, cfg: dict, names: List[str], counts: np.ndarray) -> None:
    from sentinel_tpu.core.rules import FlowRule

    r = cfg["rules"]
    c.flow_rules.load([
        FlowRule(resource=n, grade=r["grade"], count=float(k),
                 control_behavior=r["control_behavior"],
                 max_queueing_time_ms=r["max_queueing_time_ms"] + _QUEUE_OFF_MS)
        for n, k in zip(names, counts)
    ])


@contextlib.contextmanager
def control():
    """The control of this kind's cells (``study.py control``): while this
    holds, ``build`` loads every rule with a queue two milliseconds longer
    than its configuration states, so waits of 501 and 502 ms are admitted
    and a run has to come out as not correct.  Two and not one: on the chip
    ``max_queue_ms`` crosses the table gather as a float32 column rounded to
    bfloat16, and 501 arrives as 500, so a control of one millisecond came
    out ``correct`` there (PERF.md, PR 42, call D); 502 crosses whole."""
    global _QUEUE_OFF_MS
    _QUEUE_OFF_MS = 2
    try:
        yield
    finally:
        _QUEUE_OFF_MS = 0


def burst_stream(rng, cost_ms: np.ndarray, items: int, burst_max: int) -> np.ndarray:
    """``items`` topics (ranks) in a row: bursts of 1..``burst_max`` items of
    one topic, the topic drawn in proportion to its rule's own rate
    ``1000 / cost``, so every topic is offered the same share of what its
    rule lets through."""
    rate = 1000.0 / cost_ms
    cdf = np.cumsum(rate / rate.sum())
    n_bursts = int(items / ((1 + burst_max) / 2) * 1.1) + burst_max
    size = rng.integers(1, burst_max + 1, n_bursts)
    while size.sum() < items:  # 10 % of room is many deviations; never seen
        size = np.concatenate([size, rng.integers(1, burst_max + 1, n_bursts)])
    topic = np.minimum(np.searchsorted(cdf, rng.random(len(size))), len(cdf) - 1)
    return np.repeat(topic, size)[:items].astype(np.int32)


def make_pool(cfg: dict, seed: int, batch: int, ids: np.ndarray, cost_ms: np.ndarray,
              trash_row: int, param_dims: int):
    """``pool_batches`` full batches cut from ONE stream of bursts, so a
    burst that a batch's (or a block's) end cuts goes on in the next; no
    origin, no parameter value, and a service time an item."""
    tr = cfg["traffic"]
    rng = np.random.default_rng(seed)
    stream = burst_stream(rng, cost_ms, tr["pool_batches"] * batch, tr["burst_items_max"])
    no_origin = np.full(batch, trash_row, np.int32)
    no_origin_id = np.full(batch, -1, np.int32)
    ph = np.zeros((batch, param_dims), np.int32)
    pool, ranks = [], []
    for b in range(tr["pool_batches"]):
        rank = stream[b * batch:(b + 1) * batch]
        inb = (rng.random(batch) < tr["inbound_share"]).astype(np.int32)
        service = whole_ms(rng, tr["rt_ms_median"], tr["rt_sigma"], batch, tr["rt_ms_cap"])
        pool.append((ids[rank].astype(np.int32), no_origin, no_origin_id, ph, inb, service))
        ranks.append(rank)
    return pool, ranks


def build(cfg: dict, seed: int, sizes: Optional[dict] = None) -> Deployment:
    """The configuration's client (not started), its rules and its pool."""
    from sentinel_tpu.core.config import platform_engine_config
    from sentinel_tpu.runtime.client import SentinelClient

    _idle_ms()  # a program that cannot keep the guarantees stops here, in one line
    cfg = with_sizes(cfg, sizes)
    n = cfg["resources"]["n_topics"]
    c = SentinelClient(cfg=platform_engine_config(**cfg["engine"]), **cfg["client"])
    if c.cfg.max_flow_rules < n:
        raise RuntimeError(f"max_flow_rules {c.cfg.max_flow_rules} cannot hold {n} pacing rules")
    names = [topic_name(k + 1) for k in range(n)]
    # the fast topics are not the first registered: a seeded order
    order = np.random.default_rng(seed + 5).permutation(n)
    row = np.empty(n, np.int64)
    for k in order:
        rid = c.registry.resource_id(names[k])
        if rid is None or c.registry.is_sketch_id(rid):
            raise RuntimeError(
                f"{names[k]} got no exact row: max_resources {c.cfg.max_resources} less the "
                f"registry's reserve cannot hold {n} topics, and a leaky bucket needs a row of its own")
        row[k] = rid
    counts = topic_counts(cfg, seed, n)
    cost = costs_of(counts)
    _rules(c, cfg, names, counts)
    slot = np.asarray(c._rules_dev.flow.res_rules)[row, 0].astype(np.int64)
    if (slot < 0).any() or (slot >= c.cfg.max_flow_rules).any() or len(np.unique(slot)) != n:
        raise RuntimeError("a topic's pacing rule was not compiled into a slot of its own")
    pool, ranks = make_pool(cfg, seed, c.cfg.batch_size, row, cost, c.cfg.trash_row,
                            c.cfg.param_dims)
    print(json.dumps({"deployment": "pacing_client", "topics": n,
                      "max_resources": c.cfg.max_resources,
                      "rows_past": int((row > cfg["check_params"]["rows_past"]).sum()),
                      "rules_rate_sum_items_per_s": float((1000.0 / cost).sum()),
                      "topics_at_cost_1": int((cost == 1).sum()),
                      "topics_at_cost_100": int((cost == 100).sum()),
                      "pool_items": len(pool) * c.cfg.batch_size,
                      "pool_distinct_topics": int(len(np.unique(np.concatenate(ranks))))}),
          flush=True)
    return Deployment(c, cfg, pool, ranks, row, counts, cost, slot, c.cfg.batch_size)
