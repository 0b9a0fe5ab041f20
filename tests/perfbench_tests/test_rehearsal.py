"""Every cell of the committed manifest rehearsed on the CPU at a tiny size,
through the same ``run_cell`` the command calls: sizes come in as a function
argument, the command has no flag for them.  The sizes and the short
parameters are data, found by the names the cell's entry gives
(``tests/perfbench_tests/rehearsal/``), so a later PR's cell is rehearsed by
adding files.  Also the two runs of the ``single_client`` kind that must come
out as not correct: a guarantee broken in the deployment (the control), and
an answer altered where the client hands it over; another kind brings its
own, in a file of its own.

All in one file, so that one worker pays the engine compiles."""

import json
from concurrent.futures import Future

import numpy as np
import pytest

from perfbench import manifest as M
from perfbench import run
from perfbench.deployments import single_client as deployment
from tests.perfbench_tests import rehearsal

pytestmark = pytest.mark.jitted

CELLS = [w["name"] for w in M.load()["workloads"]]
REAL_RUN_CELL = run.run_cell


def rehearse(cell, seed=2**31 + 17, seconds=1.5):
    sizes, params, _names = rehearsal.of(cell)
    return run.run_cell(cell, seed, seconds, False, sizes=sizes, require_tpu=False,
                        params_override=params)


def printed(capsys):
    return [json.loads(l) for l in capsys.readouterr().out.splitlines() if l.startswith("{")]


def compared(capsys, lines=None):
    return {l["compared"]: l for l in (lines or printed(capsys)) if "rule" in l}


@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearses_correct_with_the_result_lines_shape(cell, capsys):
    result = rehearse(cell)
    lines = printed(capsys)
    numbers = compared(capsys, lines)
    assert result["correct"] is True, numbers
    # the keys the driver reads, then what stood beside a window that lost
    # requests (empty in a sound run), and last every number compared
    assert list(result) == ["correct", "attempted", "failed", "metrics", "device", "beside", "compared"]
    assert result["beside"] == {}
    assert {k: (v["value"], v["ok"]) for k, v in result["compared"].items()} == {
        k: (v["value"], v["ok"]) for k, v in numbers.items()}
    assert all(("at least" in v) != ("at most" in v) for v in result["compared"].values())
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["device"]["platform"] == "cpu" and result["device"]["count"] >= 1
    assert {"kind", "memory_peak_bytes"} <= set(result["device"])
    want = {m["name"]: m["unit"] for m in M.metrics_of(M.load(), cell, "end_to_end")}
    assert {k: v["unit"] for k, v in result["metrics"].items()} == want
    assert all(isinstance(v["value"], float) and v["value"] > 0 for v in result["metrics"].values())
    json.dumps(result)
    # beside the fullest chip's peak, every chip's own
    window = next(l for l in lines if l.get("phase") == "window")
    assert len(window["memory_peak_bytes_per_chip"]) == result["device"]["count"]
    assert max(window["memory_peak_bytes_per_chip"]) == result["device"]["memory_peak_bytes"]
    assert [l["phase"] for l in lines if "phase" in l] == ["setup", "window", "replay"]
    # every number compared is printed beside its limit, and none fails
    assert numbers and all({"value", "limit", "rule", "ok"} <= set(n) for n in numbers.values())
    assert all(n["ok"] for n in numbers.values())
    # and the check cannot have passed with nothing compared: whatever its
    # kind, something was held equal to the plain reference, and something
    # that counts what was compared reached 1
    names = rehearsal.of(cell)[2]
    assert names["equal_to_the_reference"] and names["at_least_one"]
    for name in names["equal_to_the_reference"]:
        assert numbers[name]["limit"] == 0 and numbers[name]["rule"] == "at most"
    for name in names["at_least_one"]:
        assert numbers[name]["rule"] == "at least" and numbers[name]["value"] >= 1


@pytest.mark.parametrize("cell", ["zipf-1m.paced", "zipf-10k.entry"])
def test_the_control_thresholds_one_per_cent_too_high_is_not_correct(cell, capsys):
    """The guarantee 'over-limit blocked', broken in the deployment: every
    FlowRule admits one per cent more than the configuration states."""
    real = deployment._rules
    with deployment.control():
        result = rehearse(cell)
    assert deployment._rules is real
    numbers = compared(capsys)
    assert result["correct"] is False
    assert numbers["replay_pass_count_mismatches"]["value"] >= 1


def test_an_answer_altered_where_it_is_handed_over_is_not_correct(capsys, monkeypatch):
    """The rest of a run driven with the timed path broken underneath: one
    blocked item of every block comes back as passed."""
    from sentinel_tpu.runtime.client import SentinelClient

    real = SentinelClient.submit_block

    def altered(self, res, **cols):
        inner, outer = real(self, res, **cols), Future()

        def hand_over(f):
            verdicts, waits = f.result()
            verdicts = verdicts.copy()
            blocked = np.flatnonzero(verdicts == 1)
            verdicts[blocked[:1]] = 0
            outer.set_result((verdicts, waits))

        inner.add_done_callback(hand_over)
        return outer

    monkeypatch.setattr(SentinelClient, "submit_block", altered)
    result = rehearse("zipf-1m.flood")
    numbers = compared(capsys)
    assert result["correct"] is False
    assert numbers["replay_pass_count_mismatches"]["value"] >= 1


def test_a_block_answered_late_is_counted_and_printed_and_the_run_stays_correct(capsys, monkeypatch):
    """The rest of a run driven over a standstill longer than the client's
    timeout: the block it held is answered in full, late.  The result counts
    it under ``failed``, says beside the window what was seen, and is correct,
    since no answer was wrong; ``main`` prints the same as the last lines of
    standard error."""
    import time

    from sentinel_tpu.runtime.client import SentinelClient

    real, opened = SentinelClient.submit_block, run._Hooks.opened
    state = {"open": None, "held": False}

    def noting(self):
        opened(self)
        state["open"] = time.monotonic()

    def holding(self, res, **cols):
        if state["open"] and not state["held"] and time.monotonic() - state["open"] > 0.3:
            state["held"] = True
            time.sleep(1.5)
        return real(self, res, **cols)

    monkeypatch.setattr(run._Hooks, "opened", noting)
    monkeypatch.setattr(SentinelClient, "submit_block", holding)
    sizes, params, _names = rehearsal.of("zipf-1m.flood")
    sizes = dict(sizes, client=dict(sizes["client"], entry_timeout_s=1.0))
    monkeypatch.setattr(run, "run_cell", lambda *a, **kw: REAL_RUN_CELL(
        "zipf-1m.flood", 2**31 + 19, 3.0, False, sizes=sizes, require_tpu=False, params_override=params))
    assert run.main(["--workload", "zipf-1m.flood", "--seed", "1", "--seconds", "3", "--trace", "0"]) == 0
    out = capsys.readouterr()
    result = json.loads(out.out.splitlines()[-1])
    # the held callback holds its resolver, and with it as many of the eight
    # blocks in flight as the tick thread was waiting to hand over
    assert result["correct"] is True and 1 <= result["failed"] <= 8
    assert list(result)[-2:] == ["beside", "compared"]
    beside = result["beside"]
    assert beside["answered_late"] == result["failed"] and beside["failed_block_system_or_error"] == 0
    assert beside["worst_latency_ms"] > 1500.0 and isinstance(beside["journal"], list)
    assert result["compared"]["window_failed"] == {"value": 0, "at most": 0, "ok": True}
    err = out.err.splitlines()
    assert err[-len(result["compared"]) - 1].startswith("beside the window: {")
    assert [l.split(":")[0] for l in err[-len(result["compared"]):]] == [
        f"compared {name}" for name in result["compared"]]


def test_without_a_tpu_the_command_prints_no_result(capsys):
    assert run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"]) == 2
    out = capsys.readouterr()
    assert out.out == "" and "needs a TPU" in out.err and out.err.count("\n") == 1
