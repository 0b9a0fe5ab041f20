"""The reader PR 33 brought, on a hand-made span list."""

import pytest

from tests.perfbench_tests.test_readers import ctx, span


def test_span_ratio_is_one_attribute_as_a_share_of_another_over_the_window():
    from perfbench.readers import span_ratio

    spans = [span("tick.resolve", 1, 0, 1, n_blk=64, param_rows=60, param_blocked=45),
             span("tick.resolve", 2, 10, 1, n_blk=64, param_rows=40, param_blocked=5),
             span("tick.resolve", 3, 20, 1, n_blk=64)]  # a tick without the attributes
    c = ctx()
    c.spans = spans
    assert span_ratio.read(c, "tick.resolve", "param_blocked", "param_rows") == pytest.approx(50.0)
    # a program from before the attributes: nothing to read, and no error
    assert span_ratio.read(ctx(), "tick.resolve", "param_blocked", "param_rows") is None
    c.spans = [span("tick.resolve", 1, 0, 1, param_rows=0, param_blocked=0)]
    assert span_ratio.read(c, "tick.resolve", "param_blocked", "param_rows") is None
