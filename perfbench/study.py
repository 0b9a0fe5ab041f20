"""How the paced cells' rates were found, kept so that it can be done again.

    python3 perfbench/study.py sweep --workload zipf-1m.paced --rates 1e6,2e6,3e6
    python3 perfbench/study.py run --workload zipf-1m.paced --seed 5 --seconds 30 --set rate_items_per_s=2e6
    python3 perfbench/study.py describe --workload zipf-1m.flood --out chiprun_out/xplane.txt
    python3 perfbench/study.py control --workload zipf-1m.paced --seed 5 --seconds 5

``sweep`` steps the offered rate of an open-loop cell upward in one process
(one set-up) and prints a row per step; the knee is the highest step whose
backlog does not grow (``pending_end`` no higher than ``pending_mid``) with
``failed`` 0.  The parameter it steps is the one the cell's traffic file names
under ``rate_key`` (``rate_items_per_s`` where it names none), so another
kind's knee is found with the same command.  ``run`` is one whole run of the
command with a parameter replaced, for the noise study.  ``describe`` is a traced run that also writes the
trace's planes and lines to a file.  ``control`` is a run under the
``control()`` of the cell's deployment kind, which breaks one guarantee the
configuration states (``single_client``: "over-limit blocked", every FlowRule
loaded one per cent high): it has to come out as not correct.  None of
this runs in a check: the rate a cell offers is the literal in
``perfbench/cells/<cell>.json``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from perfbench import manifest as M  # noqa: E402
from perfbench import run as R  # noqa: E402
from perfbench.generators import Hooks  # noqa: E402


def sweep(workload: str, rates, step_seconds: float, seed: int) -> None:
    cell = R.set_up(workload, seed)
    params = dict(cell.params, preroll_s=2.0, postroll_s=0.0)
    rate_key = params.get("rate_key", "rate_items_per_s")
    try:
        for i, rate in enumerate(rates):
            params[rate_key] = rate
            win = cell.generator.run(cell.dep, params, seed + i, step_seconds, Hooks())
            lat = win.latency_ms
            print(json.dumps({
                rate_key: rate,
                "visible_items_per_s": win.visible_items / win.seconds,
                "p50_ms": float(np.median(lat)) if len(lat) else None,
                "p99_ms": float(np.percentile(lat, 99)) if len(lat) else None,
                "late_p99_ms": float(np.percentile(win.late_ms, 99)),
                "attempted": win.attempted, "failed": win.failed,
                "unresolved": win.unresolved, **win.extra,
            }), flush=True)
    finally:
        cell.dep.stop()


def write_profile(describe_to: str, slice_to, slice_s: float = 0.08):
    """An ``on_profile`` for ``run_cell``: the trace's planes and lines as
    text and, if asked, the window's first ``slice_s`` seconds as JSON."""
    from perfbench import xplane

    def on_profile(profile, win, spans, mark_ns):
        with open(describe_to, "w") as f:
            f.write(xplane.describe(profile))
        if slice_to:
            cut = win.open_ns + int(slice_s * 1e9)
            with open(slice_to, "w") as f:
                json.dump({"trace": xplane.to_json(profile, slice_s), "open_ns": mark_ns,
                           "spans": [s for s in spans if s["t0_ns"] < cut]}, f)

    return on_profile


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    sub = ap.add_subparsers(dest="cmd", required=True)
    s = sub.add_parser("sweep")
    s.add_argument("--workload", required=True)
    s.add_argument("--rates", required=True)
    s.add_argument("--step-seconds", type=float, default=8.0)
    s.add_argument("--seed", type=int, default=1)
    r = sub.add_parser("run")
    r.add_argument("--workload", required=True)
    r.add_argument("--seed", type=int, required=True)
    r.add_argument("--seconds", type=float, required=True)
    r.add_argument("--set", action="append", default=[], metavar="KEY=JSON",
                   help="replace a traffic parameter, e.g. rate_items_per_s=2e6")
    c = sub.add_parser("control")
    c.add_argument("--workload", required=True)
    c.add_argument("--seed", type=int, required=True)
    c.add_argument("--seconds", type=float, default=5.0)
    d = sub.add_parser("describe")
    d.add_argument("--workload", required=True)
    d.add_argument("--out", required=True)
    d.add_argument("--seed", type=int, default=1)
    d.add_argument("--slice-out", help="also write the window's first 80 ms as JSON")
    a = ap.parse_args(argv)
    if a.cmd == "sweep":
        sweep(a.workload, [float(x) for x in a.rates.split(",")], a.step_seconds, a.seed)
    elif a.cmd == "run":
        over = {k: json.loads(v) for k, v in (kv.split("=", 1) for kv in a.set)}
        print(json.dumps(R.run_cell(a.workload, a.seed, a.seconds, False, params_override=over)))
    elif a.cmd == "control":
        kind = M.config(M.cell(M.load(), a.workload)["config"])["deployment"]
        with M.module("deployments", kind).control():
            print(json.dumps(R.run_cell(a.workload, a.seed, a.seconds, False)))
    else:
        os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
        print(json.dumps(R.run_cell(a.workload, a.seed, 4.0, True,
                                    on_profile=write_profile(a.out, a.slice_out))))
    return 0


if __name__ == "__main__":
    sys.exit(main())
