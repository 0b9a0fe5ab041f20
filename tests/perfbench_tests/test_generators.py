"""The generators' own arithmetic, against a client that answers at once:
the schedule from the seed, what counts as due in the window, lateness."""

import time
from concurrent.futures import Future

import numpy as np
import pytest

from perfbench.generators import Hooks, PassCounter
from perfbench.generators import closed_loop_blocks, open_loop_blocks
from tests.perfbench_tests.fakes import FakeDeployment

PACED = {"block_items": 64, "rate_items_per_s": 6400.0, "arrival_seed": 9,
         "preroll_s": 0.2, "postroll_s": 0.1}


class Recorder(Hooks):
    def __init__(self):
        self.calls = []

    def opened(self):
        self.calls.append("opened")

    def closed(self):
        self.calls.append("closed")


@pytest.mark.parametrize("seed", [1, 7, 2**31 + 11])
def test_schedule_is_the_seeds_and_carries_the_same_work(seed):
    a = open_loop_blocks.schedule(PACED, seed, 1.0)
    b = open_loop_blocks.schedule(PACED, seed, 1.0)
    other = open_loop_blocks.schedule(PACED, seed + 1, 1.0)
    assert (a == b).all() and (a != other).any()
    # every seed: the same number of blocks over the same time, gaps reordered
    assert len(a) == len(other) == 130
    assert abs(a[-1] - 1.3e9) < 1e3 and abs(other[-1] - 1.3e9) < 1e3
    assert np.allclose(np.sort(np.diff(a, prepend=0)), np.sort(np.diff(other, prepend=0)), atol=2)
    assert (np.diff(a) >= 0).all()


def test_block_views_cover_the_pool_without_copying():
    dep = FakeDeployment(batch=256, batches=2)
    views = open_loop_blocks.block_views(dep.pool, 64)
    assert len(views) == 8
    b, s, ids, cols, rt = views[5]
    assert (b, s) == (1, 64) and ids.base is dep.pool[1][0]
    assert cols["param_hash"].shape == (64, 2) and len(rt) == 64


def test_open_loop_counts_what_was_due_in_the_window():
    dep = FakeDeployment(delay_s=0.003)
    hooks = Recorder()
    win = open_loop_blocks.run(dep, dict(PACED), 5, 1.0, hooks)
    due = open_loop_blocks.schedule(PACED, 5, 1.0)
    in_win = ((due >= 0.2e9) & (due < 1.2e9)).sum()
    assert hooks.calls == ["opened", "closed"]
    assert win.attempted == in_win and win.failed == 0 and win.unresolved == 0
    assert dep.client.blocks == dep.client.completions == len(due)
    assert len(win.latency_ms) == in_win == len(win.late_ms)
    # latency runs from the due time: never under the client's own delay
    assert win.latency_ms.min() >= 3.0
    assert (win.late_ms >= 0).all() and np.percentile(win.late_ms, 50) < 5.0
    assert win.close_ns - win.open_ns == 1_000_000_000
    # odd ids pass, even ids are blocked, nothing else
    assert set(win.codes) == {0, 1}
    assert win.passes[::2].sum() == 0 and win.passes.sum() == win.codes[0]


def test_open_loop_charges_a_stall_to_the_blocks_it_delays():
    dep = FakeDeployment(delay_s=0.001)
    real = dep.client.submit_block
    state = {"n": 0}

    def stalling(res, **cols):
        state["n"] += 1
        if state["n"] == 40:
            time.sleep(0.15)  # the generator itself is held up
        return real(res, **cols)

    dep.client.submit_block = stalling
    win = open_loop_blocks.run(dep, dict(PACED), 3, 1.0, Hooks())
    assert win.late_ms.max() > 100.0  # it reports how late it ran
    assert win.latency_ms.max() > 100.0  # and the blocks behind the stall pay


def test_a_block_that_never_resolves_fails():
    dep = FakeDeployment(delay_s=0.001, entry_timeout_s=0.2)
    real = dep.client.submit_block
    state = {"n": 0}

    def losing(res, **cols):
        state["n"] += 1
        return Future() if state["n"] == 60 else real(res, **cols)

    dep.client.submit_block = losing
    win = open_loop_blocks.run(dep, dict(PACED), 3, 1.0, Hooks())
    assert win.failed == 1 and win.unresolved == 1
    assert len(win.latency_ms) == win.attempted - 1


def test_block_system_counts_as_failed():
    dep = FakeDeployment(delay_s=0.001)
    for b in dep.pool:
        b[0][:] = 1

    def broken(res, **cols):
        f = Future()
        f.set_result((np.full(len(res), 4, np.int8), np.zeros(len(res), np.int32)))
        return f

    dep.client.submit_block = broken
    win = open_loop_blocks.run(dep, dict(PACED), 3, 0.5, Hooks())
    assert win.failed == win.attempted > 0 and win.codes == {4: win.codes[4]}
    assert win.late == 0  # failed, and not merely late


def test_closed_loop_keeps_its_blocks_in_flight():
    dep = FakeDeployment(delay_s=0.004)
    params = {"inflight": 3, "max_blocks_per_s": 2000, "preroll_s": 0.1, "postroll_s": 0.05}
    hooks = Recorder()
    win = closed_loop_blocks.run(dep, params, 2, 0.5, hooks)
    assert hooks.calls == ["opened", "closed"]
    assert win.failed == 0 and win.unresolved == 0
    # 3 in flight, each about 4 ms: some hundreds of blocks in half a second
    assert 100 < win.attempted < 3 * 0.5 / 0.004 + 10
    assert win.visible_items == win.attempted * dep.batch
    assert dep.client.blocks == dep.client.completions


def test_a_block_answered_after_the_clients_timeout_is_late_and_says_so():
    """One standstill longer than the client's timeout: every block in flight
    is answered in full, late.  They count as failed and as late, are lost to
    the rate, and the window says how long it stood and when."""
    dep = FakeDeployment(delay_s=0.002, entry_timeout_s=0.1)
    real = dep.client.submit_block
    state = {"n": 0}

    def standing_still(res, **cols):
        state["n"] += 1
        if state["n"] == 60:
            time.sleep(0.25)  # on the thread that resolves: nothing else is answered meanwhile
        return real(res, **cols)

    dep.client.submit_block = standing_still
    params = {"inflight": 1, "max_blocks_per_s": 2000, "preroll_s": 0.05, "postroll_s": 0.05}
    win = closed_loop_blocks.run(dep, params, 2, 0.6, Hooks())
    assert win.failed == win.late == 1 and win.unresolved == 0
    assert win.visible_items == (win.attempted - 1) * dep.batch
    assert win.extra["longest_reply_gap_s"] > 0.25
    assert win.extra["worst_latency_ms"] > 250.0
    assert win.extra["failed_block_system_or_error"] == win.extra["failed_lost"] == 0


def test_the_check_holds_a_late_block_against_no_answers_limit_and_a_failed_one_still():
    import dataclasses
    import types

    from perfbench.checks import flow_replay

    dep = types.SimpleNamespace(
        config={"rules": {"flow_qps": 10.0, "tail_qps": 2.0}}, ruled_names=["a", "b"],
        tail_ids=np.array([5], np.int64))
    win = closed_loop_blocks.run(
        FakeDeployment(delay_s=0.002),
        {"inflight": 2, "max_blocks_per_s": 2000, "preroll_s": 0.05, "postroll_s": 0.05},
        2, 0.2, Hooks())
    win = dataclasses.replace(win, passes=np.zeros(8, np.int64), codes={0: 1, 1: 1})
    late = dataclasses.replace(win, failed=8, late=8)
    numbers = {n.name: n for n in flow_replay.in_window(dep, late)}
    assert numbers["window_failed"].value == 0 and all(n.ok for n in numbers.values())
    one_wrong = dataclasses.replace(win, failed=8, late=7)
    numbers = {n.name: n for n in flow_replay.in_window(dep, one_wrong)}
    assert numbers["window_failed"].value == 1 and not numbers["window_failed"].ok


def test_pass_counter_adds_up_per_engine_id():
    dep = FakeDeployment(batch=8, batches=1)
    dep.pool[0][0][:] = [3, 3, 5, 9, 9, 9, 2, 2]
    pc = PassCounter(dep.pool)
    pc.add(0, 0, np.array([0, 1, 0, 0], np.int8))
    pc.add(0, 4, np.array([0, 0, 1, 4], np.int8))
    pc.add(0, 0, np.array([0, 0, 0, 0], np.int8))
    assert pc.passes().tolist() == [0, 0, 0, 3, 0, 2, 0, 0, 0, 4]
    assert pc.code_counts() == {0: 9, 1: 2, 4: 1}


def test_the_sweep_takes_the_one_set_up_and_stops_what_it_started(monkeypatch, capsys):
    """``study.sweep`` builds nothing itself: it asks ``run.set_up`` for the
    cell, steps the rate on that deployment, and stops it."""
    import json

    from perfbench import run, study

    dep = FakeDeployment(delay_s=0.001)
    asked = []

    def set_up(workload, seed, **kw):
        asked.append((workload, seed, kw))
        return run.Cell(manifest={}, entry={}, params=dict(PACED, rate_items_per_s=1.0),
                        generator=open_loop_blocks, check=None, kind=None, dep=dep, device={},
                        clock=None, at_setup={}, root="")

    monkeypatch.setattr(run, "set_up", set_up)
    study.sweep("zipf-1m.paced", [6400.0, 12800.0], 0.3, seed=4)
    rows = [json.loads(line) for line in capsys.readouterr().out.splitlines()]
    assert asked == [("zipf-1m.paced", 4, {})] and dep.stops == 1
    assert [r["rate_items_per_s"] for r in rows] == [6400.0, 12800.0]
    assert all(r["failed"] == 0 and r["attempted"] > 0 for r in rows)
    # 2 s of pre-roll and 0.3 s of window at each step's own rate
    assert dep.client.blocks == int(2.3 * 100) + int(2.3 * 200)


def test_the_sweep_steps_the_parameter_the_traffic_file_names(monkeypatch, capsys):
    """A mix whose rate is not in items (a later kind's requests a second)
    names its own key under ``rate_key``; the sweep steps that one."""
    import json
    import types

    from perfbench import run, study
    from perfbench.generators import Window

    offered = []

    def run_mix(dep, params, seed, seconds, hooks):
        offered.append((params["requests_per_s"], params["rate_items_per_s"]))
        none = np.zeros(0)
        return Window(seconds=seconds, open_ns=0, close_ns=1, attempted=3, failed=0,
                      latency_ms=np.ones(3), due_ns=none, visible_items=3, late_ms=np.ones(3),
                      passes=none, codes={}, unresolved=0, span_s=seconds)

    params = dict(rate_key="requests_per_s", requests_per_s=1.0, rate_items_per_s=7.0)
    cell = run.Cell(manifest={}, entry={}, params=params, check=None, kind=None,
                    generator=types.SimpleNamespace(run=run_mix), dep=FakeDeployment(),
                    device={}, clock=None, at_setup={}, root="")
    monkeypatch.setattr(run, "set_up", lambda workload, seed, **kw: cell)
    study.sweep("any.cell", [100.0, 200.0], 0.1, seed=4)
    rows = [json.loads(line) for line in capsys.readouterr().out.splitlines()]
    assert offered == [(100.0, 7.0), (200.0, 7.0)]
    assert [r["requests_per_s"] for r in rows] == [100.0, 200.0]
    assert cell.dep.stops == 1 and params["requests_per_s"] == 1.0
