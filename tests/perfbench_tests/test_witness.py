"""The witness beside a window (``run.Witness``): what it keeps of a window
that stood still, and that a window that ran leaves it empty."""

import time
import types

from perfbench import run


def watched(monkeypatch, moving_s: float, still_s: float):
    monkeypatch.setattr(run.Witness, "PERIOD_S", 0.02)
    monkeypatch.setattr(run.Witness, "STALL_S", 0.2)
    hooks = run._Hooks(False)
    state = {"n": 0}
    hooks.progress = lambda: state["n"]
    witness = run.Witness(hooks)
    witness.start()
    hooks.opened()
    end = time.monotonic() + moving_s
    while time.monotonic() < end:
        state["n"] += 1
        time.sleep(0.005)
    time.sleep(still_s)
    hooks.closed()
    time.sleep(0.3)  # after the window nothing moves by design: no stall
    win = types.SimpleNamespace(open_ns=hooks.opened_ns, close_ns=hooks.closed_ns)
    line = witness.close(win)
    assert not witness.is_alive()
    return line


def test_a_window_that_stood_still_leaves_when_how_long_and_where(monkeypatch):
    line = watched(monkeypatch, 0.2, 0.5)
    assert len(line["stalls"]) == 1
    at_s, still_s = line["stalls"][0]
    assert 0.35 <= at_s <= 0.7 and 0.2 <= still_s <= 0.9
    # the frames of the thread that stood still: this test's own, asleep here
    main = [l for l in line["stalled_threads"] if l.startswith("MainThread")]
    assert main and "test_witness.py" in main[0] and ":watched" in main[0]
    assert sum(len(l) for l in line["stalled_threads"]) <= 4000


def test_a_window_that_ran_leaves_no_stall(monkeypatch):
    line = watched(monkeypatch, 0.5, 0.0)
    assert line["stalls"] == [] and "stalled_threads" not in line
    assert line["process_cpu_s"] >= 0 and line["witness_late_wakeups"] >= len(line["witness_late"])


def test_a_wake_up_that_came_late_is_kept_with_the_cpu_used_meanwhile(monkeypatch):
    monkeypatch.setattr(run.Witness, "PERIOD_S", 0.02)
    monkeypatch.setattr(run.Witness, "LATE_S", 0.0)  # every wake-up is a little late
    hooks = run._Hooks(False)
    witness = run.Witness(hooks)
    witness.start()
    hooks.opened()
    time.sleep(0.2)
    hooks.closed()
    line = witness.close(types.SimpleNamespace(open_ns=hooks.opened_ns, close_ns=hooks.closed_ns))
    assert 1 <= len(line["witness_late"]) <= 4 < line["witness_late_wakeups"]
    for at_s, late_s, cpu_s in line["witness_late"]:
        assert at_s >= 0 and late_s > 0 and cpu_s >= 0
    assert line["witness_late"] == sorted(line["witness_late"], key=lambda x: -x[1])
