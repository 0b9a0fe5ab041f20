"""Every generator rehearsed on the CPU at a tiny size, through the same
``run_cell`` the command calls: sizes come in as a function argument, the
command has no flag for them.  Also the two runs that must come out as not
correct: a guarantee broken in the deployment (the control), and an answer
altered where the client hands it over.

All in one file, so that one worker pays the engine compiles."""

import json
from concurrent.futures import Future

import numpy as np
import pytest

from perfbench import manifest as M
from perfbench import run
from perfbench.deployments import single_client as deployment

pytestmark = pytest.mark.jitted

ENGINE = dict(max_resources=112, max_nodes=120, max_flow_rules=112, max_degrade_rules=112,
              max_param_rules=8, batch_size=512, complete_batch_size=512)
SIZES = {
    "zipf-1m": {
        "engine": ENGINE,
        "resources": dict(n_ruled=48, id_universe=4095, n_tail_ruled=16),
        "rules": dict(flow_qps=100.0, tail_qps=2.0, n_param_ruled=8, n_authority_ruled=4),
        "traffic": dict(pool_batches=4),
        "client": dict(entry_timeout_s=30.0),
    },
    "zipf-10k": {
        "engine": ENGINE,
        "resources": dict(n_ruled=48, id_universe=48),
        "rules": dict(flow_qps=100.0),
        "traffic": dict(pool_batches=4),
        "client": dict(entry_timeout_s=30.0),
    },
}
SHORT = {"prime_seconds": 0.3, "preroll_s": 0.5, "postroll_s": 0.2}
PARAMS = {
    "flood-128k": dict(SHORT, max_blocks_per_s=4000, replay={"ticks": 12, "step_ms": 60, "blocks_per_tick": [1]}),
    "paced-4k": dict(SHORT, block_items=64, rate_items_per_s=12800,
                     replay={"ticks": 40, "step_ms": 25, "blocks_per_tick": [1, 3, 2, 6]}),
    "entry-8t": dict(SHORT, replay={"ticks": 100, "step_ms": 5}),
}
CELLS = [w["name"] for w in M.load()["workloads"]]


def rehearse(cell, seed=2**31 + 17, seconds=1.5):
    entry = M.cell(M.load(), cell)
    return run.run_cell(cell, seed, seconds, False, sizes=SIZES[entry["config"]],
                        require_tpu=False, params_override=PARAMS[entry["traffic"]])


def printed(capsys):
    return [json.loads(l) for l in capsys.readouterr().out.splitlines() if l.startswith("{")]


def compared(capsys, lines=None):
    return {l["compared"]: l for l in (lines or printed(capsys)) if "compared" in l}


@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearses_correct_with_the_result_lines_shape(cell, capsys):
    result = rehearse(cell)
    lines = printed(capsys)
    numbers = compared(capsys, lines)
    assert result["correct"] is True, numbers
    assert sorted(result) == ["attempted", "correct", "device", "failed", "metrics"]
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
    # every number compared is printed beside its limit, and the replay bit
    assert numbers["replay_pass_count_mismatches"]["limit"] == 0
    assert numbers["replay_blocked_items"]["value"] >= 1
    assert numbers["replay_pairs_compared"]["value"] >= 1
    assert all(n["ok"] for n in numbers.values())


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


def test_without_a_tpu_the_command_prints_no_result(capsys):
    assert run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"]) == 2
    out = capsys.readouterr()
    assert out.out == "" and "needs a TPU" in out.err and out.err.count("\n") == 1
