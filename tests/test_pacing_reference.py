"""``SentinelClient`` under RATE_LIMITER rules (sync mode, virtual clock) held
to the plain leaky bucket ``perfbench/reference/plain_pacer.py`` item for item
(verdict code and ``wait_ms``) and plane for plane (every rule's
``latestPassedTime`` after every tick), at every tick shape: each case the
benchmark's check counts (``perfbench/checks/pace_replay.py``) is a case here,
worked at a small size.  The deployment is the benchmark's own
(``perfbench/deployments/pacing_client.py``) built with a virtual clock.

One client for the whole file: a case starts from every bucket idle
(``reset_buckets``) at an engine time of its own."""

import numpy as np
import pytest

from perfbench import manifest as M
from perfbench.deployments import pacing_client
from perfbench.reference.plain_pacer import BLOCK_FLOW, PASS, PASS_WAIT, PlainPacer
from sentinel_tpu.ops import wire
from sentinel_tpu.utils.time_source import VirtualTimeSource

pytestmark = pytest.mark.jitted

SEED = 2**31 + 17
#: a batch of 2,048 has three tick shapes: 256, 512 and 2,048 rows
SIZES = {
    "engine": {"max_resources": 240, "max_nodes": 248, "max_flow_rules": 240, "max_degrade_rules": 16,
               "max_param_rules": 8, "batch_size": 2048, "complete_batch_size": 2048},
    "resources": {"n_topics": 192},
    "traffic": {"pool_batches": 2, "burst_items_max": 16},
    "check_params": {"rows_past": 96},
}
PAST_2_24 = (1 << 24) + 1  # odd: the first millisecond a float32 cannot hold
PAST_2_30 = (1 << 30) + 12_345


@pytest.fixture(scope="module")
def paced():
    vt = VirtualTimeSource(start_ms=1_000)
    sizes = dict(SIZES, client={"mode": "sync", "time_source": vt})
    dep = pacing_client.build(M.config("rate-limiter-pacing"), SEED, sizes)
    assert wire.tick_shapes(dep.client.cfg) == ((256, 256), (512, 512), (2048, 2048))
    yield dep, vt
    dep.client.stop()


def topic(dep, cost: int) -> int:
    """The first rank whose rule costs ``cost`` ms an item."""
    found = np.flatnonzero(dep.cost_ms == cost)
    assert len(found), f"the seeded counts hold no rule of cost {cost} ms"
    return int(found[0])


def drive(dep, vt, ticks):
    """Every bucket idle, then ``ticks`` = ``[(now_ms, ranks), ...]`` through
    the client, one ``submit_block`` (one tick) each, beside the reference.
    Returns the reference (its ``seen`` says what the case held), per tick
    ``(waiting rows, whole-column reads it took, shape)``, and the items by
    verdict code."""
    c = dep.client
    dep.reset_buckets()
    ref = PlainPacer(dep.counts.tolist(), dep.config["rules"]["max_queueing_time_ms"])
    per_tick, codes = [], np.zeros(7, np.int64)
    for now_ms, ranks in ticks:
        ranks = np.asarray(ranks, np.int64)
        vt.set_ms(now_ms)
        reads = dep.wait_overflow_ticks()
        verdicts, waits = c.submit_block(dep.ids[ranks].astype(np.int32)).result(timeout=60.0)
        want_v, want_w = ref.tick(now_ms, ranks.tolist())
        assert verdicts.tolist() == want_v, f"verdicts at {now_ms}"
        assert waits.tolist() == want_w, f"waits at {now_ms}"
        assert dep.latest_passed().tolist() == ref.latest, f"latestPassedTime after {now_ms}"
        assert ((waits > 0) == (verdicts == PASS_WAIT)).all()
        codes += np.bincount(verdicts, minlength=7)
        per_tick.append((int((waits > 0).sum()), dep.wait_overflow_ticks() - reads,
                         wire.tick_shape_for(c.cfg, len(ranks), 0)))
    return ref, per_tick, codes


def bursts(dep, seed: int, items: int) -> np.ndarray:
    return pacing_client.burst_stream(np.random.default_rng(seed), dep.cost_ms, items, 16)


def idle_pass(dep):
    return [(5_000, np.arange(0, 192, 3))], {"idle_passes": 64}


def reanchor(dep):
    k = topic(dep, 100)
    # 3 items own 0..200 ms; at +299 the next is a millisecond early, at +401 its cost has lapsed
    return [(5_000, [k] * 3), (5_299, [k]), (5_401, [k]), (90_000, [k])], {"reanchored": 2}


def limit_500_and_501(dep):
    k = topic(dep, 1)
    return [(7_000, [k] * 503)], {"waits_of_exactly_the_limit": 1, "refused_one_ms_past_the_limit": 2}


def carried_backlog(dep):
    slow, fast = topic(dep, 100), topic(dep, 1)
    ticks = [(9_000, [slow] * 3 + [fast] * 40), (9_025, [fast] * 30 + [slow] * 4), (9_050, [slow, fast])]
    return ticks, {"backlogs_carried_over": 4}


def cost_1_and_cost_100(dep):
    a, b = topic(dep, 1), topic(dep, 100)
    return [(11_000, [a, b] * 8), (11_004, [b, a, a, b])], {"items_at_cost_1": 10, "items_at_cost_100": 10}


def past_2_24(dep):
    """From the first odd millisecond past 2^24 on, a millisecond at a time:
    a float32 ``latestPassedTime`` lost every 1 ms cost here."""
    a, b = topic(dep, 1), topic(dep, 3)
    ticks = [(PAST_2_24 + 2 * i, [a] * (1 + i % 5) + [b] * 2) for i in range(24)]
    return ticks + [(PAST_2_24 + 5_000, [a] * 503)], {"waits_of_exactly_the_limit": 1, "reanchored": 1}


def past_2_30(dep):
    a = topic(dep, 1)
    return [(PAST_2_30, [a] * 9), (PAST_2_30 + 3, [a] * 9), (PAST_2_30 + 600, [a])], {"reanchored": 1}


CASES = [idle_pass, reanchor, limit_500_and_501, carried_backlog, cost_1_and_cost_100, past_2_24, past_2_30]


@pytest.mark.parametrize("case", CASES, ids=lambda f: f.__name__)
def test_the_client_equals_the_plain_pacer_in_a_counted_case(paced, case):
    dep, vt = paced
    ticks, at_least = case(dep)
    ref, _per_tick, _codes = drive(dep, vt, ticks)
    short = {k: (ref.seen[k], v) for k, v in at_least.items() if ref.seen[k] < v}
    assert not short, f"the case did not hold what it is named for: {short}"


@pytest.mark.parametrize("items, column_reads", [(40, 0), (64, 0), (100, 1), (600, 1)],
                         ids=["sidecar_40", "sidecar_at_its_capacity", "whole_column_100", "whole_column_600"])
def test_the_waits_come_home_by_the_sidecar_or_by_the_whole_column(paced, items, column_reads):
    """One burst on a 1 ms rule: all but its first item wait, so ``items``
    sets the tick's waiting rows.  Up to ``EXC_K`` = 64 ride the fused
    read-back's sidecar; more, and the resolver reads the whole column, which
    its counter says."""
    dep, vt = paced
    k = topic(dep, 1)
    n = items + 1 if items <= wire.EXC_K else items
    _ref, per_tick, _codes = drive(dep, vt, [(20_000, [k] * n)])
    waiting, reads, _shape = per_tick[0]
    assert waiting == min(n - 1, 500)
    assert (waiting > wire.EXC_K) == bool(column_reads) and reads == column_reads


@pytest.mark.parametrize("start_ms", [30_000, PAST_2_24 + 40_000, PAST_2_30 + 40_000],
                         ids=["early", "past_2_24", "past_2_30"])
def test_seeded_bursts_agree_at_every_tick_shape(paced, start_ms):
    """The benchmark's own stream of bursts, 25 ms a tick, in tick sizes that
    run the light, the middle and the full shape: PASS_WAIT the most common
    code, some refused, every shape seen, nothing off by a millisecond."""
    dep, vt = paced
    stream = bursts(dep, start_ms, 17_000)
    sizes = [60, 200, 500, 2048, 300, 90, 1500, 2048, 700, 40, 256, 512]
    ticks, at = [], 0
    for i, n in enumerate(sizes * 2):
        ticks.append((start_ms + 25 * i, stream[at:at + n]))
        at += n
    ref, per_tick, codes = drive(dep, vt, ticks)
    assert {shape for _w, _r, shape in per_tick} == set(wire.tick_shapes(dep.client.cfg))
    assert all(reads == (waiting > wire.EXC_K) for waiting, reads, _s in per_tick)
    assert ref.seen["backlogs_carried_over"] > 0 and ref.seen["reanchored"] > 0
    # the mix a paced deployment sees: most items admitted with a wait
    share = codes / codes.sum()
    assert share[PASS_WAIT] > 0.5 and share[BLOCK_FLOW] > 0 and share[PASS] > 0


def spans_of(tick) -> dict:
    """``{span name: [attrs, ...]}`` of what ``tick()`` recorded with the tracer on."""
    from sentinel_tpu import obs

    obs.TRACER.reset()
    obs.enable()
    try:
        tick()
    finally:
        obs.disable()
    out = {}
    for s in obs.TRACER.snapshot():
        out.setdefault(s["name"], []).append(s["attrs"] or {})
    return out


def test_a_paced_tick_tells_its_spans_how_many_wait_and_what_the_column_cost(paced):
    """``tick.resolve`` carries the tick's items, PASS_WAIT and BLOCK_FLOW
    counts off the device's stats row; ``tick.readback`` the header's
    ``n_wait`` as ``wait_rows`` and, on a tick that read the whole column,
    what that read took and moved."""
    dep, vt = paced
    k = topic(dep, 1)
    dep.reset_buckets()
    vt.set_ms(50_000)
    ids = dep.ids[[k] * 503].astype(np.int32)
    got = spans_of(lambda: dep.client.submit_block(ids).result(timeout=60.0))
    (resolve,), (readback,) = got["tick.resolve"], got["tick.readback"]
    assert (resolve["items"], resolve["pass_wait"], resolve["flow_blocked"]) == (503, 500, 2)
    assert readback["wait_rows"] == 500 and readback["wait_read_ns"] > 0
    assert readback["wait_read_bytes"] == 4 * wire.tick_shape_for(dep.client.cfg, 503, 0)[0]
    vt.set_ms(60_000)
    got = spans_of(lambda: dep.client.submit_block(ids[:10]).result(timeout=60.0))
    (readback,) = got["tick.readback"]
    assert readback == {"wait_rows": 9}  # the sidecar held them: no second read, nothing timed


def test_a_tick_without_a_pacing_rule_carries_no_new_attribute(client_factory, vt):
    """The other cells' spans do not move: ``items`` and the rest ride only
    where a RATE_LIMITER rule is loaded."""
    from sentinel_tpu.core.rules import FlowRule

    c = client_factory()
    c.flow_rules.load([FlowRule(resource="plain", count=5.0)])
    rid = c.registry.resource_id("plain")
    got = spans_of(lambda: c.submit_block(np.full(9, rid, np.int32)).result(timeout=60.0))
    (resolve,), (readback,) = got["tick.resolve"], got["tick.readback"]
    assert not {"items", "pass_wait", "flow_blocked"} & set(resolve) and readback == {}


def test_a_rules_cost_is_javas_round_for_every_whole_count_and_acquire():
    """``ops/engine.pace_cost_ms`` against ``Math.round(1000 * n / count)`` in
    float64: counts 16, 80, 400 and 2,000 stand on an exact half, which a
    divide a last bit short would round down."""
    import jax.numpy as jnp

    from sentinel_tpu.ops.engine import pace_cost_ms

    counts = np.arange(1, 4001, dtype=np.float32)
    for n in (1, 2, 3, 5):
        got = np.asarray(pace_cost_ms(jnp.full_like(counts, n), jnp.asarray(counts)))
        assert (got == np.floor(1000.0 * n / counts.astype(np.float64) + 0.5)).all()
    assert [int(pace_cost_ms(jnp.float32(1), jnp.float32(k))) for k in (16, 80, 400, 2000)] == [63, 13, 3, 1]


def test_a_count_crosses_the_table_gather_whole_where_the_chip_rounds_to_bfloat16():
    """``ops/tables.bf16_parts``: each part survives a rounding to bfloat16
    (what the chip does to a table that crosses the MXU at default
    precision), and the parts sum to the count exactly.  401 alone arrived as
    400, whose pacing cost is 3 ms and not 2."""
    import jax
    import jax.numpy as jnp

    from sentinel_tpu.ops import tables as T

    counts = jnp.asarray(np.concatenate([np.arange(1, 4001), [12.5, 0.3, 65535.0, 1234.567, 1e7 + 1]]),
                         jnp.float32)
    parts = jax.jit(T.bf16_parts)(counts)
    as_the_chip_sees_them = [p.astype(jnp.bfloat16).astype(jnp.float32) for p in parts]
    assert all((a == p).all() for a, p in zip(as_the_chip_sees_them, parts))
    assert (sum(as_the_chip_sees_them) == counts).all()
    rounded = counts.astype(jnp.bfloat16).astype(jnp.float32)
    assert float(rounded[400]) == 400.0 and float(sum(as_the_chip_sees_them)[400]) == 401.0
