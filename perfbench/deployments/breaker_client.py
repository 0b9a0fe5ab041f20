"""One ``SentinelClient`` on the first chip under slow-call circuit breakers
only, as a service mesh or an RPC framework behind Sentinel's adapters has
it: every downstream service carries one slow-ratio ``DegradeRule``, and
nothing else.  Every service has an exact row (a breaker is a state, and two
services sharing a cell would block each other's callers and steal each
other's probe), so the engine's tables are sized for all of them.

The client, its entry points, the wire, the tick's shapes and the kernels are
``single_client``'s.  What is this kind's own: the rule set, a pool in which
an item carries two response times (one for while its service is healthy,
one for while it is sick), the schedule by which services take turns being
sick, and the breakers' state read back for the check.  Names, rules, pool
and schedule are made here from the seed.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
from typing import List, Optional

import numpy as np

from perfbench.deployments import with_sizes
from perfbench.deployments.param_client import zipf_cdf
from perfbench.deployments.single_client import (  # noqa: F401 (read by the harness)
    HOST_SPANS, TICK_SPAN, Columns, host_intervals, journal,
)

#: added to every rule's ``time_window`` (seconds) while ``control()`` holds
_RETRY_OFF_S = 0


@dataclasses.dataclass
class Deployment:
    client: object
    config: dict
    pool: List[Columns]  # a batch: ids, origin_node, origin_id, param_hash, inbound, healthy rt
    pool_rank: List[np.ndarray]  # per batch: every item's service, 0-based rank in the Zipf law
    pool_rt_sick: List[np.ndarray]  # per batch: every item's rt while its service is sick
    ids: np.ndarray  # engine id (= exact row) of the service of every rank
    phase_s: np.ndarray  # per rank: where in the sick cycle it starts; < 0 = never sick
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

    def sick(self, t_s: float, period_s: float, sick_s: float) -> np.ndarray:
        """Per rank: is the service sick ``t_s`` seconds into a run."""
        return (self.phase_s >= 0) & (((t_s + self.phase_s) % period_s) < sick_s)

    def breaker_states(self) -> np.ndarray:
        """Per rank, 0 CLOSED, 1 OPEN, 2 HALF_OPEN: the engine's per-rule
        breaker state as it is now (for a stopped client, between ticks).
        ``compile_degrade_rules`` hands out slots in the order of the rules,
        which ``build`` loads by rank."""
        c = self.client
        with c._engine_lock:
            return np.asarray(c._state.cb_state)[: len(self.ids)]

    def reset_breakers(self) -> None:
        """Every breaker CLOSED with no deadline (a stopped client, between
        ticks), so that a replay and its reference start from a state that is
        known and not read back from the program.  The statistic buckets are
        left as they are: a replay starts after a gap in which they lapse."""
        import jax

        c = self.client
        with c._engine_lock:
            st = c._state
            c._state = st._replace(**{
                name: jax.device_put(np.zeros(plane.shape, plane.dtype), plane.sharding)
                for name, plane in (("cb_state", st.cb_state), ("cb_retry_ms", st.cb_retry_ms))})


def service_name(k: int) -> str:
    return f"svc-{k}"


def _rules(c, cfg: dict, names: List[str]) -> None:
    from sentinel_tpu.core.rules import DegradeRule

    r = cfg["rules"]
    c.degrade_rules.load([
        DegradeRule(resource=n, grade=r["grade"], count=r["count"],
                    slow_ratio_threshold=r["slow_ratio_threshold"],
                    time_window=r["time_window"] + _RETRY_OFF_S,
                    min_request_amount=r["min_request_amount"],
                    stat_interval_ms=r["stat_interval_ms"])
        for n in names
    ])


@contextlib.contextmanager
def control():
    """The control of this kind's cells (``study.py control``): while this
    holds, ``build`` loads every rule with ``time_window`` one second longer
    than its configuration states, so a probe comes a second late and a run
    has to come out as not correct."""
    global _RETRY_OFF_S
    _RETRY_OFF_S = 1
    try:
        yield
    finally:
        _RETRY_OFF_S = 0


def whole_ms(rng, median_ms: float, sigma: float, n: int, cap_ms: int) -> np.ndarray:
    """Lognormal response times in whole milliseconds, as upstream's
    ``completeTime - createTime`` gives them, from 1 to ``cap_ms``."""
    return np.clip(np.rint(rng.lognormal(np.log(median_ms), sigma, n)), 1, cap_ms).astype(np.float32)


def make_pool(cfg: dict, seed: int, batch: int, ids: np.ndarray, trash_row: int, param_dims: int):
    """``pool_batches`` full batches: a service by Zipf over the ranks, no
    origin, no parameter value, and two response times an item."""
    tr = cfg["traffic"]
    n = len(ids)
    cdf = zipf_cdf(n, tr["zipf_a"])
    rng = np.random.default_rng(seed)
    no_origin = np.full(batch, trash_row, np.int32)
    no_origin_id = np.full(batch, -1, np.int32)
    ph = np.zeros((batch, param_dims), np.int32)
    pool, ranks, rt_sick = [], [], []
    for _ in range(tr["pool_batches"]):
        rank = np.minimum(np.searchsorted(cdf, rng.random(batch)), n - 1)
        inb = (rng.random(batch) < tr["inbound_share"]).astype(np.int32)
        healthy = whole_ms(rng, tr["rt_ms_median_healthy"], tr["rt_sigma"], batch, tr["rt_ms_cap"])
        rt_sick.append(whole_ms(rng, tr["rt_ms_median_sick"], tr["rt_sigma"], batch, tr["rt_ms_cap"]))
        pool.append((ids[rank].astype(np.int32), no_origin, no_origin_id, ph, inb, healthy))
        ranks.append(rank.astype(np.int32))
    return pool, ranks, rt_sick


def sick_phases(cfg: dict, seed: int, n: int) -> np.ndarray:
    """Which ranks can fall sick (1-based rank ``k > sick_above`` with
    ``k % sick_every == 1``) and where in the cycle each starts."""
    tr = cfg["traffic"]
    k = np.arange(1, n + 1)
    can = (k > tr["sick_above"]) & (k % tr["sick_every"] == 1)
    phase = np.random.default_rng(seed + 7).uniform(0.0, tr["sick_period_s"], n)
    return np.where(can, phase, -1.0)


def build(cfg: dict, seed: int, sizes: Optional[dict] = None) -> Deployment:
    """The configuration's client (not started), its rules, pool and schedule."""
    from sentinel_tpu.core.config import platform_engine_config
    from sentinel_tpu.runtime.client import SentinelClient

    cfg = with_sizes(cfg, sizes)
    n = cfg["resources"]["n_services"]
    c = SentinelClient(cfg=platform_engine_config(**cfg["engine"]), **cfg["client"])
    if c.cfg.max_degrade_rules < n:
        raise RuntimeError(f"max_degrade_rules {c.cfg.max_degrade_rules} cannot hold {n} breakers")
    names = [service_name(k + 1) for k in range(n)]
    # the hot services are not the first registered: a seeded order
    order = np.random.default_rng(seed + 5).permutation(n)
    row = np.empty(n, np.int64)
    for k in order:
        rid = c.registry.resource_id(names[k])
        if rid is None or rid > c.cfg.max_resources:
            raise RuntimeError(
                f"{names[k]} got no exact row: max_resources {c.cfg.max_resources} less the "
                f"registry's reserve cannot hold {n} breakers, and a breaker needs a row of its own")
        row[k] = rid
    _rules(c, cfg, names)
    pool, ranks, rt_sick = make_pool(cfg, seed, c.cfg.batch_size, row, c.cfg.trash_row,
                                     c.cfg.param_dims)
    phase = sick_phases(cfg, seed, n)
    items = len(pool) * c.cfg.batch_size
    sickable = sum(int((phase[r] >= 0).sum()) for r in ranks)
    print(json.dumps({"deployment": "breaker_client", "services": n,
                      "max_resources": c.cfg.max_resources, "rows_past_16368": int((row > 16368).sum()),
                      "sickable_services": int((phase >= 0).sum()),
                      "pool_items": items, "pool_items_on_sickable_share": sickable / items,
                      "pool_distinct_services": int(len(np.unique(np.concatenate(ranks))))}),
          flush=True)
    return Deployment(c, cfg, pool, ranks, rt_sick, row, phase, c.cfg.batch_size)
