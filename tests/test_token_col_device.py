"""A shard's token column on a device of its own (``TokenColumnBatcher`` /
``DefaultTokenService`` / ``ShardFleet`` ``device=`` / ``devices=``): the
ledger stays on the shard's device through ``project()`` growth and a rule
push, the fleet grants what the same fleet grants on one device, and with no
device given nothing changes.  The spans of the token path record nothing
and read no clock with tracing off, and the column's program has a stable
name.

The four-device fleet runs in a process of its own with four forced host
devices, so that it does not lean on the suite's own virtual mesh."""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from sentinel_tpu import obs
from sentinel_tpu.cluster import constants as C
from sentinel_tpu.cluster.shard import ShardFleet
from sentinel_tpu.cluster.token_service import DefaultTokenService, TokenColumnBatcher
from sentinel_tpu.core import rules as R

pytestmark = pytest.mark.jitted

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DRIVER = r"""
import json, sys
sys.path.insert(0, %(root)r)
import jax
from sentinel_tpu.cluster.shard import ShardFleet
from sentinel_tpu.core import rules as R
from sentinel_tpu.core.config import small_engine_config
from sentinel_tpu.runtime.client import SentinelClient
from sentinel_tpu.utils.time_source import VirtualTimeSource

assert len(jax.devices()) == 4, jax.devices()


def rule(fid, count):
    return R.FlowRule(resource=f"res-{fid}", count=count, cluster_mode=True,
                      cluster_flow_id=fid, cluster_threshold_type=1)


def where(fleet):
    # the device of every array of every shard's column state
    return {name: sorted({d.id for leaf in jax.tree_util.tree_leaves(svc.col._state)
                          for d in leaf.devices()})
            for name, svc in fleet.services.items()}


def drive(devices):
    made = []

    def factory():
        c = SentinelClient(cfg=small_engine_config(), time_source=VirtualTimeSource(1000), mode="sync")
        c.start()
        made.append(c)
        return c

    fleet = ShardFleet(factory, n_shards=4, devices=devices, lease_slack=0.0, timeout_ms=30000,
                       retry_interval_s=300.0, reconnect_interval_s=0.0)
    out = {"built": where(fleet), "cap": {}}
    try:
        fleet.load_flow_rules("ns", [rule(f, 2.0) for f in range(101, 105)])
        out["first"] = where(fleet)
        # 80 flows: every shard's column outgrows its first 8 rows
        flows = list(range(101, 181))
        fleet.load_flow_rules("ns", [rule(f, 1.0 + f %% 3) for f in flows])
        out["grown"] = where(fleet)
        out["cap"] = {name: svc.col._cap for name, svc in fleet.services.items()}
        grants = [[int(fleet.client.request_token(f).ok) for _ in range(4)] for f in flows]
        # a rule push that drops half the flows and changes the rest
        fleet.load_flow_rules("ns", [rule(f, 2.0) for f in flows[::2]])
        out["pushed"] = where(fleet)
        grants += [[int(fleet.client.request_token(f).ok) for _ in range(4)] for f in flows]
        many = fleet.client.request_token_many([(f, 1) for f in flows[:16]])
        grants.append([int(r.ok) for r in many])
        out["grants"] = grants
        out["owners"] = sorted({fleet.client.owner_of(f) for f in flows})
    finally:
        fleet.stop()
        for svc in fleet.services.values():
            svc.close()
        for c in made:
            c.stop()
    return out


print(json.dumps({"own": drive(jax.devices()), "one": drive(None)}))
"""


@pytest.fixture(scope="module")
def driven():
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONDONTWRITEBYTECODE="1")
    done = subprocess.run([sys.executable, "-c", DRIVER % {"root": ROOT}], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    return json.loads(done.stdout.splitlines()[-1])


@pytest.mark.parametrize("stage", ["built", "first", "grown", "pushed"])
def test_every_shards_column_state_is_on_its_own_device(driven, stage):
    """Built, after a first rule load, after ``project()`` grew the ledger
    past its first rows, and after a push that dropped and changed rules."""
    assert driven["own"][stage] == {f"shard-{i}": [i] for i in range(4)}
    assert len({tuple(v) for v in driven["one"][stage].values()}) == 1  # all on the default


def test_the_columns_did_grow_and_every_shard_owned_flows(driven):
    assert all(cap > 8 for cap in driven["own"]["cap"].values()), driven["own"]["cap"]
    assert driven["own"]["owners"] == [f"shard-{i}" for i in range(4)]


def test_a_fleet_on_four_devices_grants_what_it_grants_on_one(driven):
    own, one = driven["own"]["grants"], driven["one"]["grants"]
    assert own == one
    flat = [g for row in own for g in row]
    assert 0 < sum(flat) < len(flat)  # both verdicts were compared


def flow_rule(fid, count):
    return R.FlowRule(resource=f"res-{fid}", count=count, cluster_mode=True,
                      cluster_flow_id=fid, cluster_threshold_type=1)


@pytest.fixture()
def service(client_factory):
    svc = DefaultTokenService(client_factory())
    svc.flow_rules.load("ns", [flow_rule(f, 3.0) for f in range(1, 21)])  # grows past 8 rows
    yield svc
    svc.close()


def test_with_no_device_given_nothing_is_committed_anywhere(service):
    import jax

    assert service.col.device is None and service.shard == ""
    default = jax.devices()[0]
    assert all(leaf.devices() == {default} for leaf in jax.tree_util.tree_leaves(service.col._state))
    assert [service.request_token(7).status for _ in range(4)] == [C.STATUS_OK] * 3 + [C.STATUS_BLOCKED]


def test_a_service_given_a_device_keeps_its_ledger_there(client_factory):
    import jax

    dev = jax.devices()[-1]
    svc = DefaultTokenService(client_factory(), device=dev)
    try:
        svc.flow_rules.load("ns", [flow_rule(f, 2.0) for f in range(1, 21)])
        assert svc.col.device is dev and svc.col._cap > 8
        assert [svc.request_token(5).status for _ in range(3)] == [C.STATUS_OK] * 2 + [C.STATUS_BLOCKED]
        assert all(leaf.devices() == {dev} for leaf in jax.tree_util.tree_leaves(svc.col._state))
    finally:
        svc.close()


def test_a_fleet_takes_one_device_a_shard_or_refuses_before_it_builds(client_factory):
    import jax

    built = []

    def factory():
        built.append(1)
        return client_factory()

    with pytest.raises(ValueError, match="3 devices for 2 shards: one a shard"):
        ShardFleet(factory, n_shards=2, devices=jax.devices()[:3])
    assert built == []


# -- the token path's spans ----------------------------------------------------


def test_tracing_off_the_token_path_records_nothing_and_reads_no_clock(service, monkeypatch):
    """Every site this path gained (``token.col``, ``token.col.queue``) and
    the one it had (``token.decision``): off, a flag check each."""
    from sentinel_tpu.obs import trace as OT

    def no_clock(*_a, **_kw):
        raise AssertionError("a tracing site of the token path ran with tracing off")

    obs.TRACER.reset()
    assert not OT.TRACER.enabled
    monkeypatch.setattr(OT, "now_ns", no_clock)
    monkeypatch.setattr(OT, "stage_ns", no_clock)
    assert service.request_token(3).status == C.STATUS_OK
    granted, _observed, limit = service.col.submit(4, 2, partial=True).result(timeout=30)
    assert (granted, limit) == (2, 3.0)
    assert obs.TRACER.snapshot() == []


@pytest.fixture()
def named_service(client_factory):
    svc = DefaultTokenService(client_factory(), shard="shard-7")
    svc.flow_rules.load("ns", [flow_rule(f, 3.0) for f in range(1, 21)])
    yield svc
    svc.close()


def test_token_col_spans_account_for_every_entry_submitted(named_service):
    """``token.col`` once a device call with ``n`` live entries, the shard's
    name, the jit call and the read-back; ``token.col.queue`` once an entry.
    The worker is held while the entries queue, so calls coalesce."""
    service = named_service
    decided = service.col.decided
    obs.TRACER.reset()
    obs.enable()
    try:
        with service.col._s_lock:  # the worker takes its chunk and waits here
            futs = [service.col.submit(1 + i % 20, 1, partial=False) for i in range(300)]
        assert all(f.result(timeout=30)[0] in (0, 1) for f in futs)
    finally:
        obs.disable()
    spans = obs.TRACER.snapshot()
    col = [s for s in spans if s["name"] == "token.col"]
    queue = [s for s in spans if s["name"] == "token.col.queue"]
    assert sum(s["attrs"]["n"] for s in col) == 300 == len(queue)
    assert service.col.decided - decided == 300  # the column's own count of what it decided
    assert max(s["attrs"]["n"] for s in col) <= TokenColumnBatcher.CAPACITY
    assert len(col) >= 2  # more than one chunk's worth was queued
    for s in col:
        a = s["attrs"]
        assert set(a) == {"n", "shard", "call_ns", "read_ns"} and a["shard"] == "shard-7"
        assert 0 < a["call_ns"] and 0 < a["read_ns"] and a["call_ns"] + a["read_ns"] <= s["dur_ns"]
    # an entry's wait ends where its chunk's call begins
    starts = sorted(s["t0_ns"] for s in col)
    assert all(s["t0_ns"] + s["dur_ns"] in starts for s in queue)
    # three units a flow: the ledger granted exactly that, coalesced or not
    assert sum(f.result()[0] for f in futs) == 3 * 20


def test_the_columns_program_has_a_stable_name():
    import jax
    import jax.numpy as jnp

    from sentinel_tpu.ops import token_col as TC

    shape = lambda dt: jax.ShapeDtypeStruct((TokenColumnBatcher.CAPACITY,), dt)  # noqa: E731
    lowered = TC.jitted_decide().lower(
        TC.init_state(8), jnp.int32(0), shape(jnp.int32), shape(jnp.int32), shape(jnp.int32),
        shape(jnp.bool_), shape(jnp.bool_))
    assert f"module @jit_{TC.COLUMN_PROGRAM} " in lowered.as_text()
    assert TC.COLUMN_PROGRAM == "sentinel_token_col"


def test_a_shards_rpc_span_names_its_shard(client_factory):
    fleet = ShardFleet(client_factory, n_shards=2, lease_slack=0.0, timeout_ms=30000,
                       retry_interval_s=300.0, reconnect_interval_s=0.0)
    try:
        fleet.load_flow_rules("ns", [flow_rule(f, 5.0) for f in range(101, 109)])
        obs.TRACER.reset()
        obs.enable()
        try:
            for f in range(101, 109):
                assert fleet.client.request_token(f).ok
        finally:
            obs.disable()
        rpc = [s for s in obs.TRACER.snapshot() if s["name"] == "cluster.rpc"]
        assert len(rpc) == 8 and {s["attrs"]["shard"] for s in rpc} == {"shard-0", "shard-1"}
        cols = {s["attrs"]["shard"] for s in obs.TRACER.snapshot() if s["name"] == "token.col"}
        assert cols == {"shard-0", "shard-1"}
        assert np.all([s["attrs"]["ok"] for s in rpc])
        # each column counts what it decided, and together that is every hit
        assert sorted(svc.shard for svc in fleet.services.values()) == ["shard-0", "shard-1"]
        decided = [svc.col.decided for svc in fleet.services.values()]
        assert sum(decided) == 8 and all(decided)
    finally:
        fleet.stop()
        for svc in fleet.services.values():
            svc.close()


def test_a_fleets_client_says_each_connections_protocol_and_takes_a_new_patience(client_factory):
    fleet = ShardFleet(client_factory, n_shards=2, lease_slack=0.0, timeout_ms=30000,
                       retry_interval_s=300.0, reconnect_interval_s=0.0)
    try:
        shards = fleet.client.describe()["shards"]
        assert [s["timeout_ms"] for s in shards] == [30000, 30000]
        fleet.load_flow_rules("ns", [flow_rule(f, 5.0) for f in range(101, 109)])
        for f in range(101, 109):
            assert fleet.client.request_token(f).ok
        # the version is settled by the hello's reply, which may come after the first answer
        until = time.monotonic() + 30.0
        while time.monotonic() < until and not all(
                s["protocol"] >= 2 for s in fleet.client.describe()["shards"]):
            time.sleep(0.01)
        assert all(s["protocol"] >= 2 for s in fleet.client.describe()["shards"])
        fleet.client.set_timeout_ms(1234)
        assert [s["timeout_ms"] for s in fleet.client.describe()["shards"]] == [1234, 1234]
        assert fleet.client.request_token(101).ok
    finally:
        fleet.stop()
        for svc in fleet.services.values():
            svc.close()
