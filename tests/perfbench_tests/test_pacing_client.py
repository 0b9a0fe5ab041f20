"""The ``pacing_client`` kind: its pool of bursts, its generator against a
client that answers at once (exits for admitted items only, none before its
wait is over, a response time that holds the wait), the new metric files on a
recorded window, and the kind's cell through ``run_cell`` at rehearsal size
on the CPU, where the control (every queue two milliseconds longer) has to come
out not correct.

All in one file, so that one worker pays the engine compiles."""

import json
import threading
import time
from concurrent.futures import Future

import numpy as np
import pytest

from perfbench import manifest as M
from perfbench import run
from perfbench.deployments import pacing_client
from perfbench.generators import Hooks, Window, open_loop_paced_bursts as gen
from perfbench.readers import Context
from tests.perfbench_tests import rehearsal

pytestmark = pytest.mark.jitted

CELL = "rate-limiter-pacing.paced"
CONFIG = "rate-limiter-pacing"
PACED = {"block_items": 64, "rate_items_per_s": 12800.0, "arrival_seed": 9, "preroll_s": 0.2,
         "postroll_s": 0.1, "exit_grid_ms": 5.0}
BATCH = 512


class AnsweringClient:
    """Resolves a block 2 ms after it was submitted: an item of a topic whose
    row is a multiple of three is refused, one whose row leaves 1 passes at
    once, the others are told to wait ``row`` ms (2 to 191); and keeps every
    exit it is sent with the time it came."""

    entry_timeout_s = 2.0

    def __init__(self):
        self.exits = []  # (monotonic_ns, ids, rts)

    @staticmethod
    def answer(res):
        res = np.asarray(res)
        verdicts = np.select([res % 3 == 0, res % 3 == 1], [gen.BLOCK_FLOW, gen.PASS], gen.PASS_WAIT)
        return verdicts.astype(np.int8), np.where(verdicts == gen.PASS_WAIT, res, 0).astype(np.int32)

    def submit_block(self, res, **cols) -> Future:
        fut: Future = Future()
        timer = threading.Timer(0.002, fut.set_result, args=(self.answer(res),))
        timer.daemon = True
        timer.start()
        return fut

    def submit_completion_block(self, res, rt, **cols) -> None:
        self.exits.append((time.monotonic_ns(), np.array(res), np.array(rt)))


def small_deployment(seed):
    """The kind's rules' counts and pool at rehearsal size under the stand-in
    client: ``topic_counts``, ``costs_of`` and ``make_pool`` as ``build``
    calls them."""
    sizes = rehearsal.of(CELL)[0]
    cfg = pacing_client.with_sizes(M.config(CONFIG), sizes)
    n = cfg["resources"]["n_topics"]
    ids = np.random.default_rng(seed + 5).permutation(n) + 1
    counts = pacing_client.topic_counts(cfg, seed, n)
    cost = pacing_client.costs_of(counts)
    pool, ranks = pacing_client.make_pool(cfg, seed, BATCH, ids, cost, 248, 2)
    return pacing_client.Deployment(AnsweringClient(), cfg, pool, ranks, ids, counts, cost,
                                    np.arange(n), BATCH)


@pytest.fixture(scope="module")
def answered():
    dep = small_deployment(2**31 + 21)
    t0 = time.monotonic_ns()
    win = gen.run(dep, PACED, 2**31 + 21, 1.0, Hooks())
    return dep, win, t0


def test_bursts_are_contiguous_and_go_on_across_blocks_and_batches():
    dep = small_deployment(7)
    most = dep.config["traffic"]["burst_items_max"]
    stream = np.concatenate(dep.pool_rank)
    runs = gen.burst_lengths(stream)
    # a run is a burst, or consecutive bursts that drew the same topic: the
    # mean says the sizes are 1..most and not items drawn one by one
    assert runs.sum() == len(stream) and 0.8 * (1 + most) / 2 < runs.mean() < 1.5 * (1 + most) / 2
    assert (runs > 1).mean() > 0.8
    # cut from ONE stream: a batch's last item and the next batch's first
    # are mostly one burst, which drawing each batch apart would never give
    joins = [a[-1] == b[0] for a, b in zip(dep.pool_rank, dep.pool_rank[1:])]
    assert sum(joins) >= len(joins) // 2
    # the ids a block sends are the rows of those topics
    assert all((p[0] == dep.ids[r]).all() for p, r in zip(dep.pool, dep.pool_rank))


def test_topics_are_drawn_in_proportion_to_their_rules_own_rates():
    dep = small_deployment(11)
    items = np.bincount(np.concatenate(dep.pool_rank), minlength=len(dep.ids))
    fast, slow = dep.cost_ms <= 2, dep.cost_ms >= 50
    assert fast.any() and slow.any()
    # items a topic over its rate 1000 / cost: the same offered share, fast or slow
    share = items * dep.cost_ms
    assert 0.5 < share[fast].mean() / share[slow].mean() < 2.0
    assert items[fast].mean() > 10 * items[slow].mean()


def test_the_seed_moves_the_counts_the_topics_and_the_sizes():
    a, b, c = small_deployment(7), small_deployment(7), small_deployment(8)
    for x, y in zip(a.pool + a.pool_rank, b.pool + b.pool_rank):
        assert all((p == q).all() for p, q in zip(x, y)) if isinstance(x, tuple) else (x == y).all()
    assert (a.counts == b.counts).all() and (a.ids == b.ids).all()
    assert (a.counts != c.counts).any() and (a.ids != c.ids).any()
    assert (a.pool_rank[0] != c.pool_rank[0]).any()
    la, lc = gen.burst_lengths(np.concatenate(a.pool_rank)), gen.burst_lengths(np.concatenate(c.pool_rank))
    assert len(la) != len(lc) or (la != lc).any()
    # counts are whole numbers on [10, 1000], costs Java's round of 1000 / count
    assert a.counts.min() >= 10 and a.counts.max() <= 1000
    assert (a.cost_ms == [int(np.floor(1000.0 / k + 0.5)) for k in a.counts]).all()
    # service times are whole milliseconds from 1 to the cap
    rts = np.concatenate([p[5] for p in a.pool])
    assert (rts == np.rint(rts)).all() and rts.min() >= 1 and rts.max() <= a.config["traffic"]["rt_ms_cap"]


def test_exits_follow_verdicts_admitted_items_only(answered):
    dep, win, _t0 = answered
    sent_ids = np.concatenate([e[1] for e in dep.client.exits])
    assert len(sent_ids) == win.codes[gen.PASS] + win.codes[gen.PASS_WAIT] == win.extra["exits_sent"]
    assert (sent_ids % 3 != 0).all()  # a refused item sends nothing
    assert win.codes[gen.BLOCK_FLOW] > 0 and win.extra["exits_for_blocked_items"] == 0
    assert win.extra["exits_unsent"] == 0 and win.failed == 0 and win.unresolved == 0
    assert win.extra["waits_out_of_range"] == 0 and win.extra["waits_on_items_not_pass_wait"] == 0
    shares = [win.extra[f"window_{k}_share"] for k in ("pass", "pass_wait", "flow_blocked")]
    assert sum(shares) == pytest.approx(1.0) and min(shares) > 0


def test_no_exit_is_sent_before_its_wait_is_over_and_its_rt_holds_the_wait(answered):
    dep, win, t0 = answered
    assert win.extra["exits_sent_before_the_wait_was_over"] == 0
    cap = dep.config["traffic"]["rt_ms_cap"]
    for at_ns, ids, rts in dep.client.exits:
        wait = np.where(ids % 3 == 2, ids, 0)  # what AnsweringClient told the item
        # rt = wait + a service time of 1 ms to the cap, in whole ms
        assert (rts >= wait + 1).all() and (rts <= wait + cap).all() and (rts == np.rint(rts)).all()
        # a verdict comes 2 ms after its block, blocks are sent from t0 on: an
        # exit arrives no sooner than verdict + wait + service time
        assert at_ns - t0 >= (rts.min() + 2.0) * 1e6
    assert any((ids % 3 == 2).any() for _t, ids, _r in dep.client.exits)
    assert dep.client.exits and win.extra["exit_late_ms_mean"] < 50.0
    gaps = np.diff([e[0] for e in dep.client.exits]) / 1e6
    assert np.median(gaps) > 4.0  # the grid
    assert 0 < win.extra["window_wait_ms_p50"] <= win.extra["window_wait_ms_p95"] <= 193


def test_a_sender_counts_an_exit_handed_in_as_due_before_its_wait_is_over():
    """The count the check compares with 0 can move: a slot that sends an
    item whose wait ends after the slot's instant counts it."""
    dep = small_deployment(3)
    sender = gen.HoldSender(dep, PACED, 0)
    now = 10**9
    one = lambda x, dt: np.array([x], dt)  # noqa: E731
    sender.handed.append((one(now - 5, np.int64), one(4, np.int32), one(9.0, np.float32),
                          one(0, np.int32), one(now + 3, np.int64)))
    sender.handed.append((one(now - 5, np.int64), one(5, np.int32), one(9.0, np.float32),
                          one(0, np.int32), one(now - 7, np.int64)))
    sender._slot(now)
    assert sender.sent == 2 and sender.sent_before_wait_over == 1 and sender.pending() == 0


# -- the new metric files, on a recorded window ----------------------------

MS = 1_000_000


def span(name, trace, t0_ms, dur_ms, **attrs):
    return {"name": name, "trace": trace, "t0_ns": int(t0_ms * MS), "dur_ns": int(dur_ms * MS),
            "attrs": attrs}


SPANS = [
    span("tick.resolve", 1, 10, 1, n_obj=0, n_blk=8192, items=8192, pass_wait=7600, flow_blocked=400),
    span("tick.resolve", 2, 20, 1, n_obj=0, n_blk=4096, items=4096, pass_wait=3920, flow_blocked=100),
    span("tick.resolve", 3, 30, 1, n_obj=0, n_blk=4096),  # a program from before the attributes
    span("tick.readback", 1, 9, 0.4, wait_rows=7600, wait_read_ns=300_000, wait_read_bytes=131072),
    span("tick.readback", 2, 19, 0.3, wait_rows=3920, wait_read_ns=100_000, wait_read_bytes=131072),
    span("tick.readback", 4, 39, 0.1, wait_rows=40),  # the sidecar held them: no second read
    span("tick.readback", 3, 29, 0.1),
    span("exit.due", 0, 12, 3.0, n=100), span("exit.due", 0, 17, 5.0, n=80),
    span("pace.hold", 0, 15, 0.2, sent=100, held=9000),
]
EXPECTED = {
    "pass_wait_pct.pace": 100.0 * 11520 / 12288,
    "wait_rows_per_tick.pace": (7600 + 3920 + 40) / 3,
    "wait_column_read_ms.pace": 0.2,
    "exit_late_ms.pace": 4.0,
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
             for s in SPANS if s["name"] not in ("exit.due", "pace.hold")]
    assert read(ctx(older), **spec["args"]) is None


# -- the cell at rehearsal size -------------------------------------------


def numbers_of(capsys):
    return {l["compared"]: l for l in map(json.loads, (
        l for l in capsys.readouterr().out.splitlines() if l.startswith("{"))) if "rule" in l}


def test_the_control_every_queue_two_milliseconds_longer_is_not_correct(capsys):
    sizes, params, _names = rehearsal.of(CELL)
    with pacing_client.control():
        result = run.run_cell(CELL, 2**31 + 17, 1.5, False, sizes=sizes, require_tpu=False,
                              params_override=params)
    assert pacing_client._QUEUE_OFF_MS == 0
    numbers = numbers_of(capsys)
    assert result["correct"] is False
    # waits of 501 and 502 admitted where the reference refuses them, and the buckets moved by them
    assert numbers["replay_verdict_mismatches"]["value"] >= 1
    assert numbers["replay_wait_ms_mismatches"]["value"] >= 1
    assert numbers["replay_latest_passed_mismatches"]["value"] >= 1
    # the window's own count of waits past the limit sees it too, or saw no such wait
    bad = [k for k, n in numbers.items() if k.startswith("window_") and not n["ok"]]
    assert bad in ([], ["window_waits_out_of_range"])


def test_build_refuses_in_one_line_what_the_tables_cannot_hold():
    sizes = rehearsal.of(CELL)[0]
    few = dict(sizes, engine=dict(sizes["engine"], max_flow_rules=64))
    with pytest.raises(RuntimeError, match="cannot hold 192 pacing rules"):
        pacing_client.build(M.config(CONFIG), 1, few)
    few = dict(sizes, engine=dict(sizes["engine"], max_resources=96, max_nodes=104))
    with pytest.raises(RuntimeError, match="got no exact row"):
        pacing_client.build(M.config(CONFIG), 1, few)


def test_the_configuration_is_zipf_10ks_but_for_its_rules_and_traffic():
    mine, control = M.config(CONFIG), M.config("zipf-10k")
    for group in ("engine", "client", "window"):
        assert mine[group] == control[group]
    assert mine["traffic"]["inbound_share"] == control["traffic"]["inbound_share"]
    assert mine["reduced"] == [] and mine["architecture"] is None and len(mine["source"]) <= 200
    assert {"source", "count_lo, count_hi", "max_queueing_time_ms", "bursts", "rt",
            "an exit's rt is wait + service time"} <= set(mine["assumed"])
    # the cell's rate is a literal with the sweep beside it, at most 0.8 of the rules' own
    # sum (2.25 M items/s: the counts of 10,000 topics, drawn as build() draws them)
    own = M.traffic(M.cell(M.load(), CELL))
    assert type(own["rate_items_per_s"]) is int and own["found"]
    cost = pacing_client.costs_of(pacing_client.topic_counts(mine, 7, mine["resources"]["n_topics"]))
    assert own["rate_items_per_s"] <= 0.8 * (1000.0 / cost).sum()


def test_the_replay_compiles_nothing():
    """The replay runs on the programs the window ran on: resetting the
    buckets hands the tick a plane of the kind the engine makes itself (one
    placed with ``device_put`` made the jitted tick compile every shape again,
    180 s of a run on the chip)."""
    sizes, params, _names = rehearsal.of(CELL)
    cell = run.set_up(CELL, 2**31 + 23, sizes=sizes, require_tpu=False, params_override=params)
    try:
        cell.generator.run(cell.dep, cell.params, 2**31 + 23, 0.5, Hooks())
        cell.dep.stop()
        before = cell.clock.line()["compiles"]
        ticks = sum(1 for _ in cell.generator.replay(cell.dep, cell.params, 2**31 + 23)) - 1
        assert ticks == sum(t for t, _from in cell.params["replay"]["stretches"])
        assert cell.clock.line()["compiles"] == before
    finally:
        cell.dep.stop()
