"""The ``breaker_client`` kind: its generator against a client that answers
at once (exits for admitted items only, none before it is due, the same
schedule from the same seed), the new metric files on a recorded window, and
the kind's cell through ``run_cell`` at rehearsal size on the CPU, where the
control (every ``time_window`` a second longer) has to come out not correct.

All in one file, so that one worker pays the engine compiles."""

import json
import threading
import time
from concurrent.futures import Future

import numpy as np
import pytest

from perfbench import manifest as M
from perfbench import run
from perfbench.deployments import breaker_client
from perfbench.generators import Hooks, Window, open_loop_exit_blocks as gen
from perfbench.readers import Context
from tests.perfbench_tests import rehearsal

pytestmark = pytest.mark.jitted

CELL = "degrade-100k-slow-ratio.paced"
CONFIG = "degrade-100k-slow-ratio"
PACED = {"block_items": 64, "rate_items_per_s": 12800.0, "arrival_seed": 9, "preroll_s": 0.2,
         "postroll_s": 0.1, "exit_grid_ms": 5.0, "verdict_sample_share": 1.0}


class AnsweringClient:
    """Resolves a block 2 ms after it was submitted, blocking every item of
    a service whose row is a multiple of three, and keeps every exit it is
    sent with the time it came."""

    entry_timeout_s = 2.0

    def __init__(self):
        self.exits = []  # (monotonic_ns, ids, rts)

    def submit_block(self, res, **cols) -> Future:
        fut: Future = Future()
        verdicts = np.where(np.asarray(res) % 3 == 0, gen.BLOCK_DEGRADE, 0).astype(np.int8)
        timer = threading.Timer(0.002, fut.set_result,
                                args=((verdicts, np.zeros(len(res), np.int32)),))
        timer.daemon = True
        timer.start()
        return fut

    def submit_completion_block(self, res, rt, **cols) -> None:
        self.exits.append((time.monotonic_ns(), np.array(res), np.array(rt)))


def small_deployment(seed):
    """The kind's pool and schedule at rehearsal size under the stand-in
    client: ``make_pool`` and ``sick_phases`` as ``build`` calls them."""
    sizes = rehearsal.of(CELL)[0]
    cfg = breaker_client.with_sizes(M.config(CONFIG), sizes)
    n = cfg["resources"]["n_services"]
    ids = np.random.default_rng(seed + 5).permutation(n) + 1
    pool, ranks, rt_sick = breaker_client.make_pool(cfg, seed, 512, ids, 168, 2)
    return breaker_client.Deployment(AnsweringClient(), cfg, pool, ranks, rt_sick, ids,
                                     breaker_client.sick_phases(cfg, seed, n), 512)


@pytest.fixture(scope="module")
def answered():
    dep = small_deployment(2**31 + 21)
    t0 = time.monotonic_ns()
    win = gen.run(dep, PACED, 2**31 + 21, 1.0, Hooks())
    return dep, win, t0


def test_exits_follow_verdicts_admitted_items_only(answered):
    dep, win, _t0 = answered
    sent_ids = np.concatenate([e[1] for e in dep.client.exits])
    assert len(sent_ids) == win.codes[0] == win.extra["exits_sent"]
    assert (sent_ids % 3 != 0).all()  # a blocked item sends nothing
    assert win.codes[gen.BLOCK_DEGRADE] > 0 and win.extra["exits_for_blocked_items"] == 0
    assert win.extra["exits_unsent"] == 0 and win.failed == 0 and win.unresolved == 0
    # every admitted item of every service exits once: the multiset of ids
    assert np.bincount(sent_ids, minlength=len(win.passes)).tolist() == win.passes.tolist()


def test_no_exit_is_sent_before_it_is_due(answered):
    dep, win, t0 = answered
    # an exit's response time is a whole number of ms of at least 1, its
    # verdict came 2 ms after its block was sent, and blocks are sent from t0
    # on: so nothing can arrive within 3 ms, and a slot's items arrive at
    # least their own response time after the run began
    for at_ns, _ids, rts in dep.client.exits:
        assert at_ns - t0 >= (rts.min() + 2.0) * 1e6
    assert dep.client.exits and win.extra["exit_late_ms_mean"] < 50.0
    # the grid: slots are at least most of 5 ms apart
    gaps = np.diff([e[0] for e in dep.client.exits]) / 1e6
    assert np.median(gaps) > 4.0
    # the window's response times are the configuration's lognormal alone:
    # nothing hangs past its cap
    cap = dep.config["traffic"]["rt_ms_cap"]
    assert max(float(rts.max()) for _t, _i, rts in dep.client.exits) <= cap


def test_the_same_seed_gives_the_same_pool_phases_and_arrivals():
    a, b, c = small_deployment(7), small_deployment(7), small_deployment(8)
    for x, y in zip(a.pool + a.pool_rank + a.pool_rt_sick, b.pool + b.pool_rank + b.pool_rt_sick):
        assert all((p == q).all() for p, q in zip(x, y)) if isinstance(x, tuple) else (x == y).all()
    assert (a.phase_s == b.phase_s).all() and (a.ids == b.ids).all()
    assert (a.ids != c.ids).any() and (a.pool[0][0] != c.pool[0][0]).any()
    from perfbench.generators.open_loop_blocks import schedule

    assert (schedule(PACED, 7, 1.0) == schedule(PACED, 7, 1.0)).all()
    # sickness moves: a sickable service is sick for sick_s of every period
    tr = a.config["traffic"]
    ts = np.arange(0, tr["sick_period_s"], 0.05)
    share = np.mean([a.sick(t, tr["sick_period_s"], tr["sick_s"]) for t in ts], axis=0)
    assert np.allclose(share[a.phase_s >= 0], tr["sick_s"] / tr["sick_period_s"], atol=0.03)
    assert (share[a.phase_s < 0] == 0).all()
    # response times are whole milliseconds, and 50 is not slow where 51 is
    rts = np.concatenate([p[5] for p in a.pool] + a.pool_rt_sick)
    assert (rts == np.rint(rts)).all() and rts.min() >= 1


def test_mixed_verdicts_counts_what_no_breaker_may_do_in_one_tick():
    ids = np.array([5, 5, 5, 6, 6, 6, 7, 7, 7, 8, 8, 8])
    ok = np.array([0, 0, 0, 2, 2, 2, 2, 0, 2, 0, 2, 0])  # all pass, all blocked, one probe, two passes
    assert gen.mixed_verdicts(ids, ok) == 1
    assert gen.mixed_verdicts(ids[:9], ok[:9]) == 0


# -- the new metric files, on a recorded window ----------------------------

MS = 1_000_000


def span(name, trace, t0_ms, dur_ms, **attrs):
    return {"name": name, "trace": trace, "t0_ns": int(t0_ms * MS), "dur_ns": int(dur_ms * MS),
            "attrs": attrs}


SPANS = [
    span("tick.resolve", 1, 10, 1, n_obj=0, n_blk=8192, items=8192, degrade_blocked=512,
         cb_opened=3, cb_half_opened=1, cb_closed=0, cb_reopened=1, cb_open_now=40),
    span("tick.resolve", 2, 20, 1, n_obj=0, n_blk=4096, items=4096, degrade_blocked=256,
         cb_opened=0, cb_half_opened=2, cb_closed=1, cb_reopened=0, cb_open_now=39),
    span("tick.resolve", 3, 30, 1, n_obj=0, n_blk=4096),  # a program from before the attributes
    span("exit.due", 0, 12, 3.0, n=100), span("exit.due", 0, 17, 5.0, n=80),
]
EXPECTED = {
    "degrade_blocked_pct.degrade": 100.0 * 768 / 12288,
    "breaker_transitions_per_tick.degrade": (5 + 3) / 2,
    "exit_late_ms.degrade": 4.0,
}


def ctx(spans):
    win = Window(seconds=2.0, open_ns=0, close_ns=2 * 10**9, attempted=4, failed=0,
                 latency_ms=np.array([10.0, 20.0]), due_ns=np.zeros(2), visible_items=1000,
                 late_ms=np.array([0.5, 1.5]), passes=np.zeros(1), codes={}, unresolved=0, span_s=2.0)
    return Context(window=win, setup_s=12.5, batch=256, spans=spans)


@pytest.mark.parametrize("name", list(EXPECTED))
def test_a_new_metric_file_reads_the_recorded_window(name):
    spec = M.metric(name)
    read = M.module("readers", spec["reader"]).read
    assert read(ctx(SPANS), **spec["args"]) == pytest.approx(EXPECTED[name])
    # the parent's side: its spans without the new attributes, and no exit.due
    older = [dict(s, attrs={k: v for k, v in s["attrs"].items() if k in ("n_obj", "n_blk")})
             for s in SPANS if s["name"] != "exit.due"]
    assert read(ctx(older), **spec["args"]) is None


# -- the cell at rehearsal size -------------------------------------------


def numbers_of(capsys):
    return {l["compared"]: l for l in map(json.loads, (
        l for l in capsys.readouterr().out.splitlines() if l.startswith("{"))) if "rule" in l}


def test_the_control_every_retry_a_second_late_is_not_correct(capsys):
    sizes, params, _names = rehearsal.of(CELL)
    with breaker_client.control():
        result = run.run_cell(CELL, 2**31 + 17, 1.5, False, sizes=sizes, require_tpu=False,
                              params_override=params)
    assert breaker_client._RETRY_OFF_S == 0
    numbers = numbers_of(capsys)
    assert result["correct"] is False
    assert numbers["replay_verdict_mismatches"]["value"] >= 1
    assert numbers["replay_state_mismatches"]["value"] >= 1
    # and by nothing the window counts: the served path itself was sound
    assert all(n["ok"] for k, n in numbers.items() if k.startswith("window_"))


def test_build_refuses_in_one_line_what_the_tables_cannot_hold():
    sizes = rehearsal.of(CELL)[0]
    few = dict(sizes, engine=dict(sizes["engine"], max_resources=96, max_nodes=104))
    with pytest.raises(RuntimeError, match="got no exact row"):
        breaker_client.build(M.config(CONFIG), 1, few)
    few = dict(sizes, engine=dict(sizes["engine"], max_degrade_rules=64))
    with pytest.raises(RuntimeError, match="cannot hold 96 breakers"):
        breaker_client.build(M.config(CONFIG), 1, few)


# -- the engine against the plain breakers, on rows past the first table tile --

#: 660 services under tables of 720 rows cut into one-hot tiles of 128
#: (``mxu_n_lo``): six ``n_hi`` rows, the last from row 640 on
TILED = {"max_resources": 720, "max_nodes": 728, "max_degrade_rules": 720, "mxu_n_lo": 128,
         "batch_size": 1024, "complete_batch_size": 1024}
LAST_TILE = 640
REPLAY = {"block_items": 128, "replay": {"ticks": 280, "step_ms": 25,
                                         "tick_items": [[6, 64], [3, 512], [1, 1024]],
                                         "sick_period_s": 3.0, "sick_s": 1.8,
                                         "hung_share_sick": 0.02, "hung_rt_ms": [1000, 1200]}}


@pytest.mark.parametrize("mxu", [False, True], ids=["plain_tables", "mxu_tables"])
def test_the_engine_equals_the_plain_breakers_on_every_table_tile(mxu):
    """Seeded traffic with late exits over 660 breakers, nearly uniform so
    that the rows of the last tile move too: verdict for verdict and state
    for state, and each of the four transitions on a row of the last tile."""
    from perfbench.checks import breaker_replay

    sizes = rehearsal.of(CELL)[0]
    sizes = dict(sizes, engine=dict(sizes["engine"], use_mxu_tables=mxu, **TILED),
                 resources={"n_services": 660},
                 traffic=dict(sizes["traffic"], zipf_a=0.3, pool_batches=8),
                 check_params={"rows_past": LAST_TILE})
    seed = 2**31 + 29
    dep = breaker_client.build(M.config(CONFIG), seed, sizes)
    assert dep.client.cfg.use_mxu_tables is mxu and int(dep.ids.max()) > LAST_TILE
    try:
        ticks = list(gen.replay(dep, REPLAY, seed))
    finally:
        dep.client.stop()  # its resolver threads: a later test in this worker counts threads
    numbers, _summary = breaker_replay.compare_replay(dep, iter(ticks))
    numbers = {n.name: n for n in numbers}
    assert numbers["replay_verdict_mismatches"].value == 0
    assert numbers["replay_state_mismatches"].value == 0
    assert all(n.ok for n in numbers.values()), {k: v.value for k, v in numbers.items() if not v.ok}
    # by kind, on the last tile's rows, from the engine's own state read back
    far = dep.ids >= LAST_TILE
    before, moves = np.zeros_like(ticks[1][6]), {}
    for tick in ticks[1:]:
        after = tick[6]
        for a, b in zip(before[far].tolist(), after[far].tolist()):
            if a != b:
                moves[(a, b)] = moves.get((a, b), 0) + 1
        before = after
    assert all(moves.get(k, 0) >= 1 for k in [(0, 1), (1, 2), (2, 0), (2, 1)]), moves
