"""The check of a ``two_doors`` deployment: nothing failed, only the two
codes a door can answer, and per id as many grants over the run as the plain
rule gives for the same requests (``perfbench/reference/plain_grants.py``)."""

from __future__ import annotations

from perfbench.checks import Compared
from perfbench.deployments.two_doors import GRANTED, SPENT
from perfbench.reference.plain_grants import granted


def decide(dep, generator, params: dict, seed: int, win):
    dep.stop()
    rule = dep.config["rule"]
    requests = generator.requests(dep, params, seed, win.seconds)
    want = granted(requests, rule["ids"], rule["grants_per_request"])
    other = sum(v for k, v in win.codes.items() if k not in (GRANTED, SPENT))
    numbers = [
        Compared("window_requests", win.attempted, 1, at_least=True),
        Compared("window_failed", win.failed, 0),
        Compared("window_unresolved", win.unresolved, 0),
        Compared("window_other_codes", other, 0),
        Compared("spent_answers", win.codes.get(SPENT, 0), 1, at_least=True),
        Compared("ids_granted_otherwise_than_the_plain_rule", int((win.passes != want).sum()), 0),
    ]
    return all(n.ok for n in numbers), numbers, {"requests": int(len(requests))}
