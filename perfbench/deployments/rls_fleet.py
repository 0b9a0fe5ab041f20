"""The Envoy RLS door over a fleet of token shards, each shard's token column
on a chip of its own: ``ShouldRateLimit`` over a real gRPC socket ->
``SentinelRlsGrpcServer`` -> ``EnvoyRlsRuleManager.lookup_flow_id`` ->
``ShardedTokenClient`` (no leases, a ring of named shards) -> loopback TCP ->
``ClusterTokenServer`` -> ``DefaultTokenService`` -> ``TokenColumnBatcher`` ->
``ops/token_col.decide_batch`` on the shard's device.  Built through the
program's public constructors; nothing here decides a token.

The shards live in the harness's process, because ``obs.TRACER`` and the
profiler see one process.  Each shard's decision client (rules, clock,
timeline; it decides no flow token in this deployment) stays on JAX's default
device, is built as the configuration's ``decision_client`` says (``mode``
``sync``: no tick thread, since nothing here asks it for a tick and four idle
tick loops in one process take the interpreter from the door) and is started,
as a deployment would have it, once every rule is loaded.  Its clock is a
``HeldClock``: the real one until the check's replay holds it at stated
instants.

Descriptors are numbered ``d = domain * services + service``; the sidecars
(``nodes``) each belong to one domain, domain ``k`` holding a share of them
in proportion to ``1 / (k + 1)``.
"""

from __future__ import annotations

import contextlib
import dataclasses
import inspect
from typing import List, Optional, Sequence

import numpy as np

from perfbench.deployments import intervals, with_sizes
from sentinel_tpu.utils.time_source import TimeSource

#: the span ``TokenColumnBatcher`` records once a device call
TICK_SPAN = "token.col"
DESCRIPTOR_KEY = "destination_cluster"
_extra_count = 0  # what ``control()`` loads every descriptor's count with beyond the configuration


def host_intervals(spans: List[dict]) -> list:
    """The host spans that can explain an idle device, most specific first.
    ``token.col`` ends with the jit call and then the blocking read-back,
    nothing between and nothing after, and carries both lengths: so the two
    are placed at its end.  Then the column call as a whole, a shard's RPC
    and the door.  A door with no request in hand records no span: those
    seconds read ``host_other``."""
    col = [s for s in spans if s["name"] == TICK_SPAN]
    end = np.array([s["t0_ns"] + s["dur_ns"] for s in col], np.float64)
    read = np.array([s["attrs"].get("read_ns", 0) for s in col], np.float64)
    call = np.array([s["attrs"].get("call_ns", 0) for s in col], np.float64)
    return [
        ("token.col.read", end - read, end),
        ("token.col.call", end - read - call, end - read),
        intervals(spans, TICK_SPAN),
        intervals(spans, "cluster.rpc"),
        intervals(spans, "rls.should_rate_limit"),
    ]


def journal(t0_ns: int, t1_ns: int) -> list:
    """A shard entering or leaving its degraded state (``obs.FLIGHT``'s
    ``shard.degrade.*``) between two instants, as ``(t_ns, kind, fields)``."""
    from sentinel_tpu import obs

    return [(e["t_ns"], e["kind"], e["fields"]) for e in obs.FLIGHT.events(last=256)
            if e["kind"].startswith("shard.degrade") and t0_ns <= e["t_ns"] < t1_ns]


# -- the configuration's shapes, from its numbers ----------------------------


def domain_name(k: int) -> str:
    return f"mesh-{k:02d}"


def service_name(j: int) -> str:
    return f"svc-{j:02d}"


def domain_shares(cfg: dict) -> np.ndarray:
    """Share of the nodes (and so of the requests) in each domain."""
    w = 1.0 / np.arange(1, cfg["rules"]["domains"] + 1) ** cfg["nodes"]["domain_skew"]
    return w / w.sum()


def node_domains(cfg: dict) -> np.ndarray:
    """The domain of every node: whole nodes by largest remainder, and no
    domain without one."""
    n, share = cfg["nodes"]["n"], domain_shares(cfg)
    held = np.maximum(np.floor(share * n).astype(int), 1)
    order = np.argsort(-(share * n - held), kind="stable")
    for k in order[: max(n - held.sum(), 0)]:
        held[k] += 1
    while held.sum() > n:  # only where the floor of one was forced
        held[np.argmax(held)] -= 1
    return np.repeat(np.arange(len(share)), held)


def service_probs(cfg: dict) -> np.ndarray:
    """Zipf over a domain's services, bounded: ``q[j]`` as ``1 / (j + 1) ** a``."""
    w = 1.0 / np.arange(1, cfg["rules"]["services"] + 1) ** cfg["nodes"]["service_zipf_a"]
    return w / w.sum()


def second_probs(q: np.ndarray) -> np.ndarray:
    """``r[i, j]``: the second descriptor is ``j`` given the first is ``i``
    (the same Zipf with ``i`` taken out)."""
    r = np.tile(q, (len(q), 1))
    np.fill_diagonal(r, 0.0)
    return r / r.sum(axis=1, keepdims=True)


def expected_hits_per_s(cfg: dict, requests_per_s: float) -> np.ndarray:
    """Offered hits a second on every descriptor, ``[domains, services]``."""
    q = service_probs(cfg)
    per_request = q + cfg["nodes"]["two_descriptor_share"] * (q @ second_probs(q))
    shares = np.bincount(node_domains(cfg), minlength=cfg["rules"]["domains"]) / cfg["nodes"]["n"]
    return requests_per_s * np.outer(shares, per_request)


def sized_counts(cfg: dict, requests_per_s: float) -> np.ndarray:
    """The threshold rule the configuration's literal ``counts`` were made
    by: a domain's ``hot_services`` hottest descriptors get ``hot_factor`` of
    their expected offered hits a second, every other one ``cold_factor``,
    never under 1."""
    r = cfg["rules"]
    offered = expected_hits_per_s(cfg, requests_per_s)
    factor = np.full(offered.shape, r["cold_factor"])
    factor[:, : r["hot_services"]] = r["hot_factor"]  # services are numbered hottest first
    return np.maximum(1, np.rint(factor * offered)).astype(int)


class HeldClock(TimeSource):
    """A decision client's ``time_source``: the real clock, until ``hold(ms)``
    stops it at a stated instant (the check's replay)."""

    held_ms: Optional[int] = None

    def now_ms(self) -> int:
        return super().now_ms() if self.held_ms is None else self.held_ms

    def hold(self, ms: int) -> None:
        self.held_ms = int(ms)


@dataclasses.dataclass
class Deployment:
    config: dict
    batch: int  # entries one column call holds
    counts: np.ndarray  # the configuration's count of every descriptor, flat
    node_domain: np.ndarray  # domain of every node
    fleet: object = None
    door: object = None
    address: Optional[str] = None  # the door's, once it serves
    clients: list = dataclasses.field(default_factory=list)  # the shards' decision clients
    clocks: list = dataclasses.field(default_factory=list)  # and their clocks
    #: the sidecars: whatever the generator keeps between its runs (its
    #: worker processes and their channels); ``stop()`` closes it
    nodes: object = None
    _requests: dict = dataclasses.field(default_factory=dict)

    @property
    def shards(self) -> List[str]:
        return [f"shard-{i}" for i in range(self.config["fleet"]["shards"])]

    def request_bytes(self, domain: int, services: Sequence[int]) -> bytes:
        """One ``RateLimitRequest`` of the domain, a descriptor a service."""
        key = (domain, tuple(services))
        raw = self._requests.get(key)
        if raw is None:
            from sentinel_tpu.rls import rls_pb2 as pb

            req = pb.RateLimitRequest(domain=domain_name(domain),
                                      hits_addend=self.config["nodes"]["hits_addend"])
            for j in services:
                entry = req.descriptors.add().entries.add()
                entry.key, entry.value = DESCRIPTOR_KEY, service_name(j)
            raw = self._requests[key] = req.SerializeToString()
        return raw

    def start(self) -> None:
        """Fleet, rules, decision clients, warm columns, door: in that order,
        so that a decision client compiles its tick once, for the rules it
        ends up with."""
        import jax

        from sentinel_tpu.cluster.shard import ShardFleet
        from sentinel_tpu.core.config import platform_engine_config
        from sentinel_tpu.rls.rules import EnvoyRlsRule, RlsKeyValue, RlsResourceDescriptor
        from sentinel_tpu.rls.server import SentinelRlsGrpcServer
        from sentinel_tpu.runtime.client import SentinelClient

        cfg, f, r = self.config, self.config["fleet"], self.config["rules"]
        _room_for_sockets(3 * cfg["nodes"]["n"] + 256)
        devs = jax.devices()
        ecfg = platform_engine_config(**cfg["decision_engine"])

        def decision_client():
            self.clocks.append(HeldClock())
            self.clients.append(SentinelClient(cfg=ecfg, time_source=self.clocks[-1],
                                               **cfg["decision_client"]))
            return self.clients[-1]

        # warm=False: that warm-up is a flow token through the decision
        # client's tick, which decides none here; the columns are warmed below
        self.fleet = ShardFleet(
            decision_client, names=self.shards, warm=False,
            devices=[devs[i % len(devs)] for i in range(f["shards"])],  # a chip a shard
            vnodes=f["vnodes"], lease_slack=f["lease_slack"], timeout_ms=f["sharded_timeout_ms"],
            retry_interval_s=f["retry_interval_s"], reconnect_interval_s=f["reconnect_interval_s"])
        self.door = SentinelRlsGrpcServer(self.fleet.client, host="127.0.0.1", port=0,
                                          workers=f["door_workers"])
        loaded = self.counts.reshape(r["domains"], r["services"]) + _extra_count
        self.door.rules.load([
            EnvoyRlsRule(domain_name(k), [
                RlsResourceDescriptor([RlsKeyValue(DESCRIPTOR_KEY, service_name(j))],
                                      float(loaded[k, j]))
                for j in range(r["services"])])
            for k in range(r["domains"])])
        for c in self.clients:
            c.start()
        for svc in self.fleet.services.values():
            svc.warm()
        self._connect_shards()
        self.door.start()
        self.address = f"127.0.0.1:{self.door.port}"

    def _ask_every_shard(self) -> None:
        """One request of no rule to every shard: its answer is NO_RULE and
        debits nothing."""
        client = self.fleet.client
        asked, fid = set(), 1
        while len(asked) < len(self.shards):
            owner = client.owner_of(fid)
            if owner not in asked and self.fleet.services[owner].flow_rules.get_by_id(fid) is None:
                client.request_token(fid)
                asked.add(owner)
            fid += 1

    def _connect_shards(self) -> None:
        """Open every shard's connection, then wait until each has negotiated
        the protocol that carries batch frames."""
        import time

        self._ask_every_shard()
        until = time.monotonic() + 5.0
        while time.monotonic() < until:
            if all(s["protocol"] >= 2 for s in self.fleet.client.describe()["shards"]):
                return
            time.sleep(0.01)
        raise RuntimeError("a shard's connection did not negotiate protocol v2 within 5 s")

    def stop(self) -> None:
        """Sidecars, door, fleet, columns, decision clients; twice is harmless."""
        nodes, self.nodes = self.nodes, None
        if nodes is not None:
            nodes.close()
        door, self.door = self.door, None
        if door is not None:
            door.stop(grace=0.0)
        fleet, self.fleet = self.fleet, None
        if fleet is not None:
            fleet.stop()
            for svc in fleet.services.values():
                svc.close()
        clients, self.clients = self.clients, []
        for c in clients:
            c.stop()

    def counters(self) -> dict:
        """The program's own counters this kind's check reads, summed over
        their label sets, and per shard what its column decided
        (``TokenColumnBatcher.decided``) and what the ring routed to it, as
        they stand; an account is the difference of two readings."""
        out = {
            "shed": _counted("sentinel_token_shed_total"),
            "door_answers": _counted("sentinel_rls_requests_total"),
            "door_errors": _counted("sentinel_rls_requests_total", code="error"),
            "rpc_failures": _counted("sentinel_cluster_rpc_failures_total"),
            "degrade_transitions": _counted("sentinel_shard_degrade_transitions_total"),
            "lease_local_admits": _counted("sentinel_lease_local_admits_total"),
            "fallback_admits": _counted("sentinel_shard_fallback_total", verdict="pass"),
            "fallback_blocks": _counted("sentinel_shard_fallback_total", verdict="block"),
            "column_decisions": _counted("sentinel_cluster_batched_decisions_total"),
        }
        for name in self.shards:
            out[f"column_decisions.{name}"] = self.fleet.services[name].col.decided
            out[f"shard_requests.{name}"] = _counted("sentinel_shard_requests_total", shard=name)
        return out

    def answered(self) -> int:
        """Requests the door has answered so far (the witness asks ten
        times a second)."""
        return _counted("sentinel_rls_requests_total")

    def degraded(self) -> List[str]:
        """The shards the ring holds degraded at this instant."""
        return [name for name in self.shards if self.fleet.client.shard_degraded(name)]

    def settle(self) -> List[str]:
        """Heal what a stall left behind: a shard the ring holds degraded has
        its cooldown running on the real clock, and until a probe after it
        succeeds the ring answers that shard's hits itself.  Wait the cooldown
        out and let one request of no rule be the probe.  Returns the shards
        it found degraded: none in a sound run, which waits for nothing and
        sends nothing."""
        import time

        found = self.degraded()
        for _ in range(3):
            if not self.degraded():
                break
            time.sleep(0.05 + max(s["cooldown_remaining_s"]
                                  for s in self.fleet.client.describe()["shards"]))
            self._ask_every_shard()
        return found

    def hold_clocks(self, ms: int) -> None:
        for clock in self.clocks:
            clock.hold(ms)

    def patience(self, ms: Optional[int] = None) -> None:
        """How long the ring waits for a shard's answer from now on; ``None``
        is the configuration's.  The replay lengthens it as it lengthens the
        sidecars' deadline: it compares decisions, and an RPC given up on
        under a starved host is a failure the window's account holds at 0,
        not a decision."""
        self.fleet.client.set_timeout_ms(
            self.config["fleet"]["sharded_timeout_ms"] if ms is None else ms)

    def now_ms(self) -> int:
        """The latest of the shards' clocks."""
        return max(clock.now_ms() for clock in self.clocks)


def _counted(name: str, **labels) -> int:
    """A counter of the program's registry, summed over the label sets that
    carry ``labels``."""
    from sentinel_tpu import obs

    return int(sum(m.value for m in obs.REGISTRY.series(name)
                   if all(dict(m.labels).get(k) == v for k, v in labels.items())))


def _room_for_sockets(wanted: int) -> None:
    """Every node holds a connection, and both of its ends are in this
    process or its children: raise the soft limit on open files to what that
    takes, if the hard one allows."""
    import resource

    soft, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
    if soft >= wanted:
        return
    if hard != resource.RLIM_INFINITY and hard < wanted:
        raise RuntimeError(f"{wanted} open files needed, the hard limit is {hard}")
    resource.setrlimit(resource.RLIMIT_NOFILE, (wanted, hard))


@contextlib.contextmanager
def control():
    """The control of this kind's cells (``study.py control``): while this
    holds, every descriptor is loaded with a count one higher than the
    configuration states, so "never more than ``count`` in a window" is
    broken and a run has to come out as not correct."""
    global _extra_count
    _extra_count = 1
    try:
        yield
    finally:
        _extra_count = 0


def _require_program() -> None:
    """What this kind needs of the program, asked before anything is started:
    a program that lacks it (the parent of the PR that brought the kind) fails
    here, in one line, with no server, channel or thread behind it."""
    from sentinel_tpu.cluster.shard import ShardedTokenClient, ShardFleet
    from sentinel_tpu.ops import token_col

    missing = []
    if "devices" not in inspect.signature(ShardFleet.__init__).parameters:
        missing.append("ShardFleet(devices=...): a device for each shard's token column")
    if not hasattr(ShardedTokenClient, "set_timeout_ms"):
        missing.append("ShardedTokenClient.set_timeout_ms: the ring's patience, for the replay")
    if not hasattr(token_col, "COLUMN_PROGRAM"):
        missing.append("ops.token_col.COLUMN_PROGRAM: the column's stable program name")
    if missing:
        raise RuntimeError("the program cannot run an rls_fleet deployment; it lacks "
                           + "; ".join(missing))


def build(cfg: dict, seed: int, sizes: Optional[dict] = None) -> Deployment:
    """The deployment's data, nothing started: ``start()`` builds the fleet."""
    from sentinel_tpu.cluster.token_service import TokenColumnBatcher

    _require_program()
    cfg = with_sizes(cfg, sizes)
    r = cfg["rules"]
    counts = np.asarray(r["counts"], np.int64)
    if counts.shape != (r["domains"], r["services"]):
        raise ValueError(f"rules.counts is {counts.shape}, not {(r['domains'], r['services'])}")
    return Deployment(cfg, TokenColumnBatcher.CAPACITY, counts.reshape(-1), node_domains(cfg))
