"""flops-bytes-budget: hot-path compile-time cost gated against goldens.

The benchmark measures wall clock AFTER merge; this pass gates the
STATIC cost — XLA `cost_analysis` flops and bytes-accessed of each
budget-eligible entry point — at PR time.  A change that doubles the
tick's memory traffic (an accidental f32 upcast of a count plane, a
gather that re-materializes the one-hot in HBM) shows up as a budget
breach in CI instead of a regression in the next ledger row.

Budgets live in `sentinel_tpu/analysis/jaxpr/budgets.json` as absolute
ceilings, written by

    python -m sentinel_tpu.analysis --update-budgets

as measured * HEADROOM (25%), so routine drift passes and step-change
regressions fail.  Tightening a budget after an optimization lands is
part of that optimization's PR (run --update-budgets; ceilings shrink
to the new measurement).

Pallas-bearing entries never appear here: their CPU lowering is the
interpreter loop, whose cost model says nothing about the Mosaic kernel
(see entrypoints.PALLAS_ENTRIES).  An eligible entry that cannot be
measured (jaxlib without a cost model) is reported — the gate fails
loudly rather than silently passing a regression.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, Optional

from sentinel_tpu.analysis.framework import ERROR, Finding
from sentinel_tpu.analysis.jaxpr.framework import (
    BUDGETS_PATH,
    JaxprPass,
    TracedEntry,
    load_golden,
)

#: --update-budgets writes ceiling = measured * (1 + HEADROOM)
HEADROOM = 0.25

_METRICS = ("flops", "bytes")


class CostBudgetPass(JaxprPass):
    name = "flops-bytes-budget"
    description = "entry-point XLA cost must stay under checked-in ceilings"
    severity = ERROR

    def __init__(self, budget_path: str = BUDGETS_PATH):
        self.budget_path = budget_path
        self._golden: Optional[Dict[str, Any]] = None

    def _load(self) -> Dict[str, Any]:
        if self._golden is None:
            self._golden = load_golden(self.budget_path)
        return self._golden

    def run(self, entry: TracedEntry) -> Iterable[Finding]:
        if not entry.cost_eligible:
            return
        if entry.cost is None:
            yield self.finding(
                entry,
                "budget-eligible entry could not be measured (no XLA cost "
                "model on this jaxlib) — the cost gate is not running; fix "
                "the toolchain or mark the entry ineligible with a rationale",
            )
            return
        budgets = self._load().get("entries", {})
        want = budgets.get(entry.name)
        if want is None:
            yield self.finding(
                entry,
                "no cost budget checked in for this entry point — run "
                "`python -m sentinel_tpu.analysis --update-budgets` and "
                "commit budgets.json",
            )
            return
        for metric in _METRICS:
            ceiling = want.get(metric)
            got = entry.cost.get(metric, 0.0)
            if ceiling is not None and got > ceiling:
                yield self.finding(
                    entry,
                    f"{metric} {got:,.0f} exceeds the checked-in ceiling "
                    f"{ceiling:,.0f} (recorded at measured+{HEADROOM:.0%} "
                    "headroom) — this PR regresses the compiled hot path's "
                    "static cost.  Optimize, or if the increase is a "
                    "deliberate trade, re-baseline with --update-budgets "
                    "and justify the diff in the PR",
                )
