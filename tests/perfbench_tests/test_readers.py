"""The span readers on a hand-made span list."""

import numpy as np
import pytest

from perfbench.generators import Window
from perfbench.readers import Context, span_fill, span_join, span_period, span_stat, window_stat

MS = 1_000_000


def span(name, tick, t0_ms, dur_ms, **attrs):
    return {"name": name, "trace": tick, "t0_ns": int(t0_ms * MS),
            "dur_ns": int(dur_ms * MS), "attrs": attrs}


#: three ticks, 10 ms apart; tick 3 has not resolved yet
SPANS = [
    span("tick.assemble", 1, 0, 2), span("tick.presort", 1, 1, 3),
    span("tick.dispatch", 1, 5, 1), span("tick.resolve", 1, 30, 2, n_obj=0, n_blk=64),
    span("tick.assemble", 2, 10, 2), span("tick.presort", 2, 11, 5),
    span("tick.dispatch", 2, 17, 1), span("tick.resolve", 2, 50, 4, n_obj=8, n_blk=120),
    span("tick.assemble", 3, 20, 4), span("tick.dispatch", 3, 24, 1),
    span("client.recompile_rules", 0, 3, 1),
]


def ctx(**kw):
    win = Window(seconds=2.0, open_ns=0, close_ns=2 * 10**9, attempted=4, failed=0,
                 latency_ms=np.array([10.0, 20.0, 30.0, 40.0]), due_ns=np.zeros(4),
                 visible_items=1000, late_ms=np.array([0.5, 1.5]), passes=np.zeros(1),
                 codes={}, unresolved=0, span_s=2.0)
    return Context(window=win, setup_s=12.5, batch=256, spans=SPANS, **kw)


def test_span_stat_sums_the_named_spans_of_each_tick():
    got = span_stat.read(ctx(), ["tick.assemble", "tick.presort", "tick.dispatch"])
    assert got == pytest.approx((6 + 8 + 5) / 3)
    assert span_stat.read(ctx(), ["tick.presort"], stat="p50") == pytest.approx(4.0)


def test_span_period_is_start_to_start():
    assert span_period.read(ctx(), "tick.assemble") == pytest.approx(10.0)
    assert span_period.read(ctx(), "tick.resolve") == pytest.approx(20.0)


def test_span_join_pairs_on_the_tick_id_and_skips_the_unjoined():
    got = span_join.read(ctx(), ["tick.dispatch", "end"], ["tick.resolve", "end"])
    assert got == pytest.approx(((32 - 6) + (54 - 18)) / 2)
    got = span_join.read(ctx(), ["tick.assemble", "start"], ["tick.dispatch", "start"], stat="p99")
    assert 5.0 < got <= 7.0


def test_span_fill_is_items_over_the_tick_width():
    got = span_fill.read(ctx(), "tick.resolve", ["n_obj", "n_blk"])
    assert got == pytest.approx(100.0 * (64 + 128) / 2 / 256)


def test_window_stat_reads_the_generators_numbers():
    c = ctx()
    assert window_stat.read(c, "setup_s") == 12.5
    assert window_stat.read(c, "rate", "visible_items") == 500.0
    assert window_stat.read(c, "p50", "latency_ms") == 25.0
    assert window_stat.read(c, "p99", "late_ms") == pytest.approx(1.49)


@pytest.mark.parametrize("reader, args", [
    (span_stat, {"spans": ["tick.nothing"]}),
    (span_period, {"span": "tick.nothing"}),
    (span_join, {"start": ["tick.nothing", "end"], "end": ["tick.resolve", "end"]}),
    (span_fill, {"span": "tick.nothing", "attrs": ["n_blk"]}),
])
def test_a_reader_that_finds_nothing_returns_nothing(reader, args):
    assert reader.read(ctx(), **args) is None
