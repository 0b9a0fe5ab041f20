"""What the ``rls_fleet`` kind brings beside its rehearsal data (the cell's
own rehearsal is a case of ``test_rehearsal.py``): its two runs that must
come out as not correct, the order of what it compares, how its traced run
is read, and the parent's refusal."""

import json

import numpy as np
import pytest

from perfbench import manifest as M
from perfbench import xplane
from perfbench.deployments import host_intervals
from perfbench.deployments import rls_fleet as kind
from perfbench.readers import Context
from tests.perfbench_tests.test_rehearsal import compared, printed, rehearse

pytestmark = pytest.mark.jitted

CELL = "rls-mesh-4096.paced"
ORDER = [
    "window_requests", "window_failed", "window_unresolved", "window_shed", "window_door_errors",
    "window_rpc_failures", "window_degrade_transitions", "window_shards_degraded_at_open_or_close",
    "window_lease_local_admits", "window_fallback_answers", "window_column_decisions_off_hits_sent",
    "window_shards_whose_column_decided", "window_over_admitted_descriptors", "window_ok_answers",
    "window_over_limit_answers",
    "replay_hits_compared", "replay_pairs_compared", "replay_two_descriptor_requests",
    "replay_unanswered", "replay_granted_mismatches", "replay_answers_held_to_the_over_limit_count",
    "replay_over_limit_count_off_the_reference", "replay_overall_code_disagreements",
    "replay_ok_answers", "replay_over_limit_answers",
]


def test_every_count_one_higher_is_not_correct_and_the_numbers_come_in_order(capsys):
    """The guarantee "never more than ``count`` in a window", broken in the
    deployment (``study.py control``): the replay's exact comparison bites,
    the window's loose one does not."""
    with kind.control():
        result = rehearse(CELL)
    assert kind._extra_count == 0
    lines = printed(capsys)
    numbers = compared(capsys, lines)
    assert result["correct"] is False
    assert list(result["compared"]) == ORDER == list(numbers)
    assert numbers["replay_granted_mismatches"]["value"] >= 1
    # a count one higher admits more and refuses fewer: the two comparisons
    # with the reference bite, and nothing of the window's account
    failing = [n for n, v in numbers.items() if not v["ok"]]
    assert failing[0] == "replay_granted_mismatches" and set(failing) <= {
        "replay_granted_mismatches", "replay_over_limit_count_off_the_reference"}, result["beside"]
    # a run that is not correct says what stood beside it: the columns decided
    # the hits sent after the window opened, give or take those in flight then
    beside = result["beside"]
    assert 0 <= beside["moved.column_decisions"] - beside["hits_answered"] <= beside["hits_across_open"]
    assert beside["hits_unanswered"] == 0 and beside["healed_in_preroll"] == []
    assert beside["degraded_at_open"] == beside["degraded_at_close"] == []
    assert all(beside[f"moved.column_decisions.shard-{i}"] > 0 for i in range(4))
    replay = next(l for l in lines if l.get("phase") == "replay")
    assert replay["steps"] == 30 and replay["moved.rpc_failures"] == 0


def test_an_answer_altered_at_the_door_is_not_correct(capsys, monkeypatch):
    """One refused descriptor of every tenth refused answer comes back as
    admitted, its overall code left as it was: the exact comparison catches
    the descriptor, the door's own rule the answer."""
    from sentinel_tpu.rls import rls_pb2 as pb
    from sentinel_tpu.rls.server import SentinelEnvoyRlsService

    real, seen = SentinelEnvoyRlsService._decide, [0]

    def altered(self, request):
        rsp = real(self, request)
        if rsp.overall_code == pb.RateLimitResponse.OVER_LIMIT:
            seen[0] += 1
            if seen[0] % 10 == 0:
                next(s for s in rsp.statuses if s.code == pb.RateLimitResponse.OVER_LIMIT).code = (
                    pb.RateLimitResponse.OK)
        return rsp

    monkeypatch.setattr(SentinelEnvoyRlsService, "_decide", altered)
    result = rehearse(CELL)
    numbers = compared(capsys)
    assert result["correct"] is False
    assert numbers["replay_granted_mismatches"]["value"] >= 1
    assert numbers["replay_overall_code_disagreements"]["value"] >= 1


def _window(dep, **changed):
    """A sound window's account over two shards, with ``changed`` laid over it."""
    from perfbench.generators import Window

    extra = dict.fromkeys(["shed", "door_errors", "rpc_failures", "degrade_transitions",
                           "lease_local_admits", "fallback_admits", "fallback_blocks"], 0)
    extra.update({"column_decisions": 100, "column_decisions.shard-0": 60,
                  "column_decisions.shard-1": 40, "shard_requests.shard-0": 60,
                  "shard_requests.shard-1": 40})
    extra = {f"moved.{k}": v for k, v in extra.items()}
    extra.update(hits_answered=100, hits_unanswered=0, hits_across_open=0,
                 degraded_at_open=[], degraded_at_close=[])
    late = changed.pop("late", 0)
    extra.update(changed)
    return Window(seconds=1.0, open_ns=0, close_ns=1, attempted=80, failed=late,
                  latency_ms=np.ones(80), due_ns=np.zeros(80), visible_items=80, late_ms=np.zeros(80),
                  passes=np.array([3, 4]), codes={1: 70, 2: 10}, unresolved=0, span_s=1.2,
                  late=late, extra=extra)


SOUND = ("sound", {}, None)
STALLS = [
    SOUND,
    # an answer past the sidecar's deadline is late, not wrong ...
    ("late", {"late": 3, "hits_answered": 96, "hits_unanswered": 4, "moved.column_decisions": 98,
              "moved.column_decisions.shard-0": 58}, None),
    # ... but nothing that a stall leaves one level down is excused by it
    ("rpc", {"late": 3, "moved.rpc_failures": 1}, "window_rpc_failures"),
    ("degrade", {"moved.degrade_transitions": 1}, "window_degrade_transitions"),
    ("open", {"degraded_at_open": ["shard-1"]}, "window_shards_degraded_at_open_or_close"),
    ("close", {"degraded_at_close": ["shard-0"]}, "window_shards_degraded_at_open_or_close"),
    ("fallback_block", {"moved.fallback_blocks": 1}, "window_fallback_answers"),
    ("fallback_admit", {"moved.fallback_admits": 1}, "window_fallback_answers"),
    ("lease", {"moved.lease_local_admits": 1}, "window_lease_local_admits"),
    ("shed", {"moved.shed": 1}, "window_shed"),
    # the columns decided fewer hits than were answered: something else answered
    ("short", {"moved.column_decisions": 99}, "window_column_decisions_off_hits_sent"),
    # or more than were sent, the unanswered and those in flight at the opening counted in
    ("beyond", {"moved.column_decisions": 104, "hits_unanswered": 2, "hits_across_open": 1},
     "window_column_decisions_off_hits_sent"),
    ("within", {"moved.column_decisions": 103, "hits_unanswered": 2, "hits_across_open": 1}, None),
    # the ring routed to both shards, and one shard's column decided nothing
    ("idle_column", {"moved.column_decisions.shard-1": 0}, "window_shards_whose_column_decided"),
]


@pytest.mark.parametrize("case", STALLS, ids=[c[0] for c in STALLS])
def test_the_windows_account_holds_every_trace_of_a_stall_to_zero(case):
    """The window's account is strict: a failed RPC, a shard that was or
    became degraded, an answer by the ring's fallback, a hit no column
    decided, a shard whose column decided nothing, each alone, is not
    correct, with or without late answers beside it."""
    import types

    from perfbench.checks import token_replay

    _name, changed, failing = case
    dep = types.SimpleNamespace(shards=["shard-0", "shard-1"], counts=np.array([5, 5]))
    numbers = token_replay.in_window(dep, _window(dep, **changed))
    assert [n.name for n in numbers if not n.ok] == ([failing] if failing else [])
    assert [n.name for n in numbers] == ORDER[:len(numbers)]


def test_a_shard_left_degraded_by_the_pre_rolls_first_part_is_healed_before_the_window(capsys, monkeypatch):
    """What a traced run's profiler start does on the chip: a stall early in
    the pre-roll, an RPC given up on, a shard degraded.  The generator heals
    it between the pre-roll's two parts, says so beside the window, and the
    window's account, which starts when the window opens, is sound."""
    real_settle, seen = kind.Deployment.settle, []

    def degraded_once(self):
        if not seen:
            # as the ring does on a failed RPC (``_enter_degraded``), cooldown 0.2 s
            client = self.fleet.client
            client.retry_interval_s = 0.2
            client._enter_degraded(client._shards["shard-2"])
            client.retry_interval_s = self.config["fleet"]["retry_interval_s"]
        seen.append(self.degraded())
        return real_settle(self)

    monkeypatch.setattr(kind.Deployment, "settle", degraded_once)
    result = rehearse(CELL)
    lines = printed(capsys)
    assert seen[0] == ["shard-2"] and all(s == [] for s in seen[1:])
    window = next(l for l in lines if l.get("phase") == "window")
    assert window["healed_in_preroll"] == ["shard-2"]
    assert window["degraded_at_open"] == window["degraded_at_close"] == []
    # the enter was before the window's first reading, the probe's exit too
    assert window["moved.degrade_transitions"] == 0 and window["moved.fallback_blocks"] == 0
    assert window["moved.column_decisions.shard-2"] > 0
    assert result["correct"] is True, result["compared"]


def test_a_program_without_a_device_for_a_shard_is_refused_before_anything_starts(monkeypatch):
    """The parent of the PR that brought the kind: ``build`` asks first."""
    import threading

    from sentinel_tpu.cluster import shard

    class Before:
        def __init__(self, client_factory, n_shards=2, **sharded_kw):
            raise AssertionError("the fleet was built")

    monkeypatch.setattr(shard, "ShardFleet", Before)
    before = set(threading.enumerate())
    with pytest.raises(RuntimeError, match=r"cannot run an rls_fleet deployment; it lacks ShardFleet\(devices="):
        kind.build(M.config("rls-mesh-4096"), 1)
    assert set(threading.enumerate()) <= before  # an earlier test's thread may end meanwhile


def test_the_kinds_spans_place_the_call_and_the_read_back_at_the_end_of_token_col():
    def span(name, t0, dur, **attrs):
        return {"name": name, "t0_ns": t0, "dur_ns": dur, "trace": 0, "attrs": attrs}

    spans = [
        span("rls.should_rate_limit", 0, 1000),
        span("cluster.rpc", 100, 800, shard="shard-1"),
        span("token.col", 300, 500, n=3, shard="shard-1", call_ns=150, read_ns=250),
        span("token.col.queue", 250, 50),
    ]
    host = host_intervals(kind, spans)
    assert [(n, list(a), list(b)) for n, a, b in host] == [
        ("token.col.read", [550.0], [800.0]), ("token.col.call", [400.0], [550.0]),
        ("token.col", [300.0], [800.0]), ("cluster.rpc", [100.0], [900.0]),
        ("rls.should_rate_limit", [0.0], [1000.0])]
    assert kind.TICK_SPAN == "token.col"
    # an idle device under them: each name gets what the ones before it left
    pd = xplane.from_json({"planes": [
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
            {"name": xplane.WINDOW_MARK, "start_ns": 0, "duration_ns": 1200}]}]},
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
            {"name": "%fusion.1 = s32[8]{0} fusion(s32[8]{0} %a)", "start_ns": 560, "duration_ns": 40}]}]},
    ]})
    assert xplane.idle_by(pd, 0, host) == {
        "token.col.read": pytest.approx(210e-9), "host_other": pytest.approx(200e-9),
        "cluster.rpc": pytest.approx(300e-9), "rls.should_rate_limit": pytest.approx(200e-9),
        "token.col.call": pytest.approx(150e-9), "token.col": pytest.approx(100e-9), "in_program": 0.0}


def test_the_new_readers_read_the_spans_and_return_nothing_where_there_are_none():
    spans = [{"name": "token.col", "t0_ns": 0, "dur_ns": 2_000_000, "trace": 0,
              "attrs": {"n": n, "read_ns": 500_000 * n, "call_ns": 1, "shard": "shard-0"}}
             for n in (1, 3)]
    ctx = Context(window=None, setup_s=1.0, batch=256, spans=spans)

    def read(metric, c=ctx):
        spec = M.metric(metric)
        return M.module("readers", spec["reader"]).read(c, **spec["args"])

    assert read("col_entries_per_call.mesh") == 2.0
    assert read("col_read_ms.mesh") == pytest.approx(1.0)
    assert read("col_call_ms.mesh") == pytest.approx(2.0)
    # a program without these spans (the parent): nothing to read, and no error
    empty = Context(window=None, setup_s=1.0, batch=256)
    for metric in ("col_entries_per_call.mesh", "col_read_ms.mesh", "col_call_ms.mesh",
                   "col_queue_ms.mesh", "door_ms.mesh", "shard_rpc_ms.mesh", "device_col_ms.mesh"):
        assert read(metric, empty) is None


def test_the_journal_keeps_a_shards_degrade_transitions_only():
    from sentinel_tpu import obs

    t0 = obs.now_ns()
    obs.FLIGHT.note("shard.degrade.enter", shard="shard-2")
    obs.FLIGHT.note("ruleset.compile", n=1)
    kinds = [(k, f.get("shard")) for _t, k, f in kind.journal(t0, obs.now_ns() + 1)]
    assert kinds == [("shard.degrade.enter", "shard-2")]
