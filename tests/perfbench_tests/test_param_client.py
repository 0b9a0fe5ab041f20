"""The ``param_client`` kind at a small size on the CPU: the served path
(``SentinelClient.submit_block`` through the compiled tick) against the exact
shadow ``perfbench/reference/param_shadow.py`` on seeded traffic, the plain
reference on its own, and the two runs of the kind that must come out as not
correct.

All in one file, so that one worker pays the engine compiles."""

import json

import numpy as np
import pytest

from perfbench import manifest as M
from perfbench import run
from perfbench.checks import param_replay
from perfbench.deployments import param_client
from perfbench.generators import open_loop_param_blocks
from perfbench.reference.param_shadow import ParamShadow, pair_keys
from tests.perfbench_tests import rehearsal

pytestmark = pytest.mark.jitted

CELL = "param-1m-hot-keys.paced"
REPLAY = {"block_items": 64, "replay": {"ticks": 48, "step_ms": 25, "blocks_per_tick": [2, 4, 3]}}


def replayed(width, seed=2**31 + 33, **resources):
    """A deployment at rehearsal size with the store ``width`` cells wide,
    never started (``tick_once`` compiles on its first call), a replay of its
    traffic at virtual times, and the check's verdict on it."""
    sizes, _params, _names = rehearsal.of(CELL)
    sizes = dict(sizes, engine=dict(sizes["engine"], param_width=width),
                 resources=dict(sizes["resources"], **resources))
    dep = param_client.build(M.config("param-1m-hot-keys"), seed, sizes)
    ticks = open_loop_param_blocks.replay(dep, REPLAY, seed)
    numbers, summary = param_replay.compare_replay(dep, ticks)
    return dep, ticks, {n.name: n for n in numbers}, summary


def per_pair(dep, ticks):
    """``{(tick time, pair key): (items, admitted)}`` of a replay."""
    out = {}
    for now_ms, ids, values, verdicts in ticks:
        keys = pair_keys(ids, values)
        for k in np.unique(keys).tolist():
            mine = keys == k
            out[(now_ms, k)] = (int(mine.sum()), int((verdicts[mine] == 0).sum()))
    return out


@pytest.fixture(scope="module")
def wide():
    """2^15 cells (a wide store: two stretches) under 800 pairs."""
    return replayed(1 << 15)


def test_where_nothing_collides_the_served_path_equals_the_shadow_pair_for_pair(wide):
    dep, ticks, numbers, _summary = wide
    assert all(n.ok for n in numbers.values()), numbers
    assert numbers["replay_param_over_admitted"].value == 0
    assert numbers["replay_param_false_block_share"].value == 0.0
    # and not through the check's own sums: an independent walk, pair by pair
    rule, item = dep.thresholds()
    shadow = ParamShadow(rule, item, 500, 2)
    compared = 0
    for now_ms, ids, values, verdicts in ticks:
        keys = pair_keys(ids, values)
        uniq, _n, allowed = shadow.tick(now_ms, keys)
        got = np.array([int((verdicts[keys == k] == 0).sum()) for k in uniq.tolist()])
        assert (got == allowed).all()
        shadow.admit(now_ms, uniq, got)
        compared += len(uniq)
    assert compared > 1000


def test_both_verdict_codes_and_no_third(wide):
    _dep, ticks, _numbers, _summary = wide
    codes = np.unique(np.concatenate([t[3] for t in ticks]))
    assert codes.tolist() == [0, param_replay.BLOCK_PARAM]


def test_an_exception_items_pair_is_held_to_its_own_threshold(wide):
    dep, ticks, numbers, _summary = wide
    rule, item = dep.thresholds()
    assert numbers["replay_item_keys_past_the_rules_count"].value >= 1
    # over any two neighbouring buckets an item's pair admits at most its
    # own 10, and somewhere more than the rule's 5; every other pair at most 5
    buckets = {}
    for (now_ms, k), (_n, got) in per_pair(dep, ticks).items():
        buckets[(k, now_ms // 500)] = buckets.get((k, now_ms // 500), 0) + got
    worst_item = worst_other = 0
    for (k, b), got in buckets.items():
        window = got + buckets.get((k, b - 1), 0)
        if k in item:
            worst_item = max(worst_item, window)
        else:
            worst_other = max(worst_other, window)
    assert 5 < worst_item <= 10 and worst_other == 5


def test_where_pairs_collide_the_store_errs_to_one_side_only():
    """512 cells a depth under 800 pairs: an estimate counts a cell's other
    pairs too, so the path blocks what an exact count would admit (counted)
    and never admits what it would block."""
    _dep, _ticks, numbers, summary = replayed(512)
    assert numbers["replay_param_over_admitted"].value == 0
    assert numbers["replay_param_false_block_share"].value > 0.01
    assert not numbers["replay_param_false_block_share"].ok
    assert summary["admissions_refused"] >= 1


def test_the_control_every_count_one_higher_is_not_correct(capsys):
    sizes, params, _names = rehearsal.of(CELL)
    with param_client.control():
        result = run.run_cell(CELL, 2**31 + 17, 1.5, False, sizes=sizes, require_tpu=False,
                              params_override=params)
    assert param_client._COUNT_OFF == 0
    numbers = {l["compared"]: l for l in map(json.loads, (
        l for l in capsys.readouterr().out.splitlines() if l.startswith("{"))) if "rule" in l}
    assert result["correct"] is False
    assert numbers["replay_param_over_admitted"]["value"] >= 1
    assert numbers["window_over_admitted_pairs"]["rule"] == "at most"


def test_a_block_answered_as_passed_where_it_was_blocked_is_not_correct(monkeypatch, capsys):
    """The timed path broken underneath: one blocked item of every block
    comes back as passed, in the window and in the replay."""
    from concurrent.futures import Future

    from sentinel_tpu.runtime.client import SentinelClient

    real = SentinelClient.submit_block

    def altered(self, res, **cols):
        inner, outer = real(self, res, **cols), Future()

        def hand_over(f):
            verdicts, waits = f.result()
            verdicts = verdicts.copy()
            verdicts[np.flatnonzero(verdicts == param_replay.BLOCK_PARAM)[:1]] = 0
            outer.set_result((verdicts, waits))

        inner.add_done_callback(hand_over)
        return outer

    monkeypatch.setattr(SentinelClient, "submit_block", altered)
    sizes, params, _names = rehearsal.of(CELL)
    result = run.run_cell(CELL, 2**31 + 19, 1.5, False, sizes=sizes, require_tpu=False,
                          params_override=params)
    capsys.readouterr()
    assert result["correct"] is False
    assert result["compared"]["replay_param_over_admitted"]["value"] >= 1


def test_build_states_the_universe_and_counts_the_pools_pairs(wide, capsys):
    dep, _ticks, _numbers, summary = wide
    assert dep.universe == 16 * 50 and 0 < dep.pool_pairs <= dep.universe
    pool_keys = np.concatenate([pair_keys(b[0], b[3][:, 0]) for b in dep.pool])
    assert dep.pool_pairs == len(np.unique(pool_keys))
    assert summary["pairs_admitted"] <= dep.pool_pairs
    # every item carries a value, and its route's exception item names the
    # route's most frequent client
    assert all((b[3][:, 0] != 0).all() for b in dep.pool)
    rule, item = dep.thresholds()
    assert len(rule) == 16 and len(item) == 16
    hottest = {}
    for k in pool_keys.tolist():
        hottest.setdefault(k >> 32, {}).setdefault(k, 0)
        hottest[k >> 32][k] += 1
    top = {max(v, key=v.get) for v in hottest.values() if max(v.values()) > 20}
    assert top and top <= set(item)


def test_a_program_without_the_wide_store_is_refused_in_one_line(monkeypatch):
    from sentinel_tpu.ops import param as store

    monkeypatch.delattr(store, "wide")
    cfg = M.config("param-1m-hot-keys")
    with pytest.raises(RuntimeError, match=f"cannot hold param_width {cfg['engine']['param_width']}") as e:
        param_client.build(cfg, 1)
    assert "\n" not in str(e.value)


# -- the plain reference on its own ---------------------------------------


def shadow(**items):
    return ParamShadow({1: 5.0, 2: 3.0}, {int(k): v for k, v in items.items()}, 500, 2)


def test_shadow_admits_up_to_the_threshold_in_a_window_of_two_buckets():
    s = shadow()
    key = pair_keys(np.array([1]), np.array([77]))[0]
    keys = np.full(4, key)
    uniq, n, allowed = s.tick(1000, keys)
    assert (uniq.tolist(), n.tolist(), allowed.tolist()) == ([key], [4], [4])
    s.admit(1000, uniq, allowed)
    assert s.tick(1400, keys)[2].tolist() == [1]  # same bucket: 4 of 5 spent
    s.admit(1400, uniq, np.array([1]))
    assert s.tick(1600, keys)[2].tolist() == [0]  # next bucket, same window
    assert s.tick(2000, keys)[2].tolist() == [4]  # the first bucket has left: 5 - 1
    assert s.tick(2500, keys)[2].tolist() == [4]


def test_shadow_keeps_rules_and_values_apart_and_honours_an_item():
    a, b, c = pair_keys(np.array([1, 1, 2]), np.array([9, 10, 9])).tolist()
    s = shadow(**{str(b): 10.0})
    uniq, n, allowed = s.tick(0, np.array([a] * 7 + [b] * 12 + [c] * 7))
    assert dict(zip(uniq.tolist(), allowed.tolist())) == {a: 5, b: 10, c: 3}
    assert s.threshold(a) == 5.0 and s.threshold(b) == 10.0 and s.threshold(c) == 3.0


def test_shadow_is_told_what_was_admitted_not_what_it_allowed():
    s = shadow()
    key = int(pair_keys(np.array([1]), np.array([5]))[0])
    s.admit(0, np.array([key]), np.array([2]))  # the program admitted 2 of the 5 allowed
    assert s.window(key, 100) == 2 and s.tick(100, np.array([key] * 9))[2].tolist() == [3]
    s.forget_before(1600)
    assert s.counts == {}
