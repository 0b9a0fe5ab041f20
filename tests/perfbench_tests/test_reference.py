"""The plain reference on hand-worked cases, against a per-item loop and
against the repository's LeapArray oracle."""

import numpy as np
import pytest

from perfbench.reference.leap import FlowReference, LeapWindows
from tests.oracle import OracleLeapArray


def test_reference_imports_nothing_of_the_program():
    import perfbench.reference.leap as leap

    src = open(leap.__file__).read()
    assert "sentinel_tpu" not in src and "import jax" not in src


@pytest.mark.parametrize(
    "times, items, want",
    [
        # threshold 3: the first tick admits 3 of 5, the window is then full
        ([1000, 1100, 1400], [5, 2, 1], [3, 0, 0]),
        # the second bucket still sees the first: nothing until 1 s has passed
        ([1000, 1600, 1999], [3, 4, 4], [3, 0, 0]),
        # at 2000 the bucket that started at 1000 has left the window
        ([1000, 1600, 2000, 2100], [2, 4, 4, 4], [2, 1, 2, 0]),
        # a long gap empties both buckets
        ([1000, 5000], [9, 9], [3, 3]),
    ],
)
def test_hand_worked_windows(times, items, want):
    ref = FlowReference([7], [3.0], 2, 500)
    got = [int(ref.tick(t, np.full(n, 7))[2][0]) for t, n in zip(times, items)]
    assert got == want


def test_unruled_ids_always_pass_and_rows_resolve():
    ref = FlowReference([5, 900001], [1.0, 2.0], 2, 500)
    uniq, n, passes = ref.tick(1000, np.array([5, 5, 8, 900001, 900001, 900001, 8]))
    assert uniq.tolist() == [5, 8, 900001]
    assert n.tolist() == [2, 2, 3]
    assert passes.tolist() == [1, 2, 2]
    assert ref.rows_of(np.array([4, 5, 900001, 900002])).tolist() == [-1, 0, 1, -1]


def _loop_reference(ticks, thresholds, sample_count=2, window_ms=500):
    """Item by item, bucket by bucket: the slow way the vector form must equal."""
    buckets = {}  # (resource, bucket start) -> passes
    out = []
    for now, ids in ticks:
        start = now // window_ms * window_ms
        passes = {}
        for r in ids:
            thr = thresholds.get(int(r))
            seen = sum(
                v for (rr, s), v in buckets.items()
                if rr == r and 0 <= now - s < sample_count * window_ms
            )
            if thr is None or seen + 1 <= thr:
                if thr is not None:
                    buckets[(r, start)] = buckets.get((r, start), 0) + 1
                passes[int(r)] = passes.get(int(r), 0) + 1
            else:
                passes.setdefault(int(r), 0)
        out.append(passes)
    return out


@pytest.mark.parametrize("seed", [1, 2, 3, 2**31 + 5])
def test_vector_form_equals_the_loop(seed):
    rng = np.random.default_rng(seed)
    thresholds = {r: float(rng.integers(1, 30)) for r in range(1, 9)}
    now, ticks = 10_000, []
    for _ in range(60):
        now += int(rng.integers(1, 400))
        ticks.append((now, rng.integers(1, 12, rng.integers(1, 40))))
    ref = FlowReference(list(thresholds), list(thresholds.values()), 2, 500)
    want = _loop_reference(ticks, thresholds)
    for (t, ids), w in zip(ticks, want):
        uniq, _n, passes = ref.tick(t, ids)
        assert dict(zip(uniq.tolist(), passes.tolist())) == w


@pytest.mark.parametrize("seed", [4, 5])
def test_windows_agree_with_the_repository_oracle(seed):
    rng = np.random.default_rng(seed)
    mine, oracle = LeapWindows(6, 2, 500), OracleLeapArray(6, 2, 500)
    now = 777
    for _ in range(200):
        now += int(rng.integers(0, 700))
        rows = rng.integers(0, 6, 5)
        n = rng.integers(1, 4, 5)
        mine.add(now, rows, n)
        for r, k in zip(rows, n):
            oracle.add(now, int(r), 0, int(k))
        assert mine.window(now).tolist() == oracle.window_event(now, 0).tolist()


def test_window_must_be_one_second():
    with pytest.raises(ValueError):
        FlowReference([1], [1.0], 3, 500)
