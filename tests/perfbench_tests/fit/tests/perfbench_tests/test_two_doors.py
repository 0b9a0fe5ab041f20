"""What a kind brings beside its rehearsal data: its own run that must come
out as not correct, here the ``two_doors`` cells under the kind's own
``control()`` (both doors loaded with two grants a request where the
configuration states one)."""

import json

import pytest

from perfbench.deployments import two_doors
from tests.perfbench_tests.test_rehearsal import rehearse


@pytest.mark.parametrize("cell", ["pair.round-robin", "pair.fast"])
def test_two_grants_a_request_is_not_correct(cell, capsys):
    with two_doors.control():
        result = rehearse(cell)
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines() if l.startswith("{")]
    numbers = {l["compared"]: l for l in lines if "rule" in l}
    assert result["correct"] is False
    assert numbers["ids_granted_otherwise_than_the_plain_rule"]["value"] >= 1
