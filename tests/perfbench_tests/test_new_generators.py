"""The generators PR 33 brought, against a client that answers at once: the
burst's schedule from its phases and the seed, and the admissions per pair
that the param generator keeps."""

import numpy as np
import pytest

from perfbench.generators import Hooks, open_loop_blocks
from tests.perfbench_tests.fakes import FakeDeployment

PACED = {"block_items": 64, "rate_items_per_s": 6400.0, "arrival_seed": 9,
         "preroll_s": 0.2, "postroll_s": 0.1}


class Recorder(Hooks):
    def __init__(self):
        self.calls = []

    def opened(self):
        self.calls.append("opened")

    def closed(self):
        self.calls.append("closed")


# -- the burst: paced-4k with the rate raised for a stretch ---------------

BURST = {"block_items": 4096, "rate_items_per_s": 1_250_000, "arrival_seed": 20260927,
         "preroll_s": 3.0, "postroll_s": 0.5, "phases_window_s": 30.0,
         "phases": [{"from_s": 10.0, "to_s": 15.0, "rate_x": 2.0}]}


@pytest.mark.parametrize("seed", [3, 2**31 + 5])
def test_burst_schedule_holds_the_stated_blocks_in_each_phase(seed):
    from perfbench.generators import open_loop_blocks_burst as burst

    assert burst.stretches(BURST, 30.0) == [
        (0.0, 13.0, 1_250_000), (13.0, 18.0, 2_500_000.0), (18.0, 33.5, 1_250_000)]
    due = burst.schedule(BURST, seed, 30.0) / 1e9
    assert (np.diff(due) >= 0).all() and abs(due[-1] - 33.5) < 1e-6
    # 305.17 blocks a second at the base rate, twice that in the burst (a
    # stretch's last block is due at its end, to a rounding)
    eps = 1e-6
    before, upto = (due < 13.0 + eps).sum(), (due < 18.0 + eps).sum()
    assert (before, upto - before, len(due) - upto) == (3967, 3051, 4730)
    in_window = (due >= 3.0) & (due < 33.0)
    share = ((due >= 13.0) & (due < 18.0)).sum() / in_window.sum()
    assert 0.28 < share < 0.29  # 29 % of the window's blocks fall in the burst
    # the seed moves the gaps inside a stretch and never a stretch
    other = burst.schedule(BURST, seed + 1, 30.0) / 1e9
    assert len(other) == len(due) and (other != due).any()
    assert (other < 13.0 + eps).sum() == before and (other < 18.0 + eps).sum() == upto


def test_burst_keeps_its_shares_in_a_shorter_window():
    from perfbench.generators import open_loop_blocks_burst as burst

    assert burst.stretches(BURST, 3.0) == [
        (0.0, 4.0, 1_250_000), (4.0, 4.5, 2_500_000.0), (4.5, 6.5, 1_250_000)]
    due = burst.schedule(BURST, 1, 3.0) / 1e9
    assert ((due >= 4.0 + 1e-6) & (due < 4.5 + 1e-6)).sum() == int(0.5 * 2_500_000 / 4096)


def test_burst_runs_open_loop_blocks_on_its_own_schedule_and_puts_it_back():
    from perfbench.generators import open_loop_blocks_burst as burst

    params = dict(PACED, phases_window_s=1.0, phases=[{"from_s": 0.4, "to_s": 0.6, "rate_x": 3.0}])
    dep = FakeDeployment(delay_s=0.003)
    real = open_loop_blocks.schedule
    win = burst.run(dep, params, 5, 1.0, Recorder())
    assert open_loop_blocks.schedule is real
    due = burst.schedule(params, 5, 1.0)
    # 130 blocks at the base rate, and 40 more for the 0.2 s at three times it
    assert dep.client.blocks == len(due) and 168 <= len(due) <= 170
    assert win.attempted == ((due >= 0.2e9) & (due < 1.2e9)).sum() and win.failed == 0


def test_param_generator_sums_admissions_per_pair_from_the_pool_items():
    from perfbench.generators import open_loop_param_blocks as gen
    from perfbench.reference.param_shadow import pair_keys

    ids = np.array([1, 1, 2, 1], np.int32)
    ph = np.array([[7, 0], [7, 0], [7, 0], [9, 0]], np.int32)
    pool = [(ids, None, None, ph, None, None), (ids, None, None, ph, None, None)]
    keys, n = gen.pair_admissions(pool, [np.array([2, 1, 0, 4]), np.array([0, 5, 3, 0])])
    want = dict(zip(pair_keys([1, 2, 1], [7, 7, 9]).tolist(), [8, 3, 4]))
    assert dict(zip(keys.tolist(), n.tolist())) == want
    real = open_loop_blocks.PassCounter
    kept = []
    with gen._kept_counter(kept):
        made = open_loop_blocks.PassCounter(FakeDeployment().pool)
    assert kept == [made] and open_loop_blocks.PassCounter is real


# -- the cell zipf-1m.burst, once a manifest names it -----------------------


def test_the_burst_cell_runs_by_adding_its_entries_to_the_manifest(tmp_path):
    """``zipf-1m.burst``'s generator, traffic, cell and rehearsal files are in
    the tree; its entries of ``BENCHMARK.json`` are kept as data
    (``data/zipf-1m.burst.add.json``) because the manifest's own tests list
    ``zipf-1m``'s cells by name.  Over a copy of the manifest with the entries
    appended, and nothing else changed, the cell rehearses correct through
    the same ``run_cell``, on the served path."""
    import json
    import os
    import shutil

    from perfbench import manifest as M
    from perfbench import run
    from tests.perfbench_tests import rehearsal

    with open(os.path.join(os.path.dirname(__file__), "data", "zipf-1m.burst.add.json")) as f:
        add = json.load(f)
    root = str(tmp_path)
    shutil.copytree(os.path.join(M.ROOT, M.HERE), os.path.join(root, M.HERE),
                    ignore=shutil.ignore_patterns("__pycache__"))
    grown = M.load()
    grown["workloads"] = grown["workloads"] + add["workloads"]
    grown["per_layer"] = grown["per_layer"] + add["per_layer"]
    for m in grown["end_to_end"]:
        m.get("workloads", []).extend(add["end_to_end_workloads"].get(m["name"], []))
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(grown, f)
    for name, body in add["metric_files"].items():
        with open(os.path.join(root, M.HERE, "metrics", f"{name}.json"), "w") as f:
            json.dump(body, f)
    assert M.problems(M.load(root), root) == []
    cell = add["workloads"][0]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        "zipf-1m.burst", "zipf-1m", "burst-4k", 1)
    params = M.traffic(cell, root)
    assert params["generator"] == "open_loop_blocks_burst" and params["rate_items_per_s"] == 1_250_000
    assert params["phases"] == [{"from_s": 10.0, "to_s": 15.0, "rate_x": 2.0}]
    sizes = rehearsal.read("configs", "zipf-1m")
    short = rehearsal.read("traffic", "burst-4k")
    result = run.run_cell("zipf-1m.burst", 2**31 + 29, 1.5, False, sizes=sizes,
                          require_tpu=False, params_override=short, root=root)
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"decision_p50_ms", "decision_p95_ms", "setup_s"}
