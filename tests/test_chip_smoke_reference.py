"""chip_smoke.py's equivalence phase compares the served deployment with the
same deployment on the plain scatter engine.  In a file of its own: tier-1
hands files to its workers in order of their test count, and
tests/test_chip_smoke.py keeps the place in that order it had (PERF.md,
PR 29)."""

from __future__ import annotations

import numpy as np

import chip_smoke


def test_the_equivalence_reference_is_the_same_deployment_on_the_plain_engine():
    """Same file, same seed, same traffic; only the engine's fast-path flags
    and the pipelining differ."""
    sizes = (chip_smoke.REHEARSAL_SIZES, {"client": {"mode": "sync"}})
    served, plain = chip_smoke.build(3, *sizes), chip_smoke.build(3, *sizes, chip_smoke._PLAIN)
    try:
        differ = {
            k for k in served.config["engine"] | plain.config["engine"]
            if getattr(served.client.cfg, k) != getattr(plain.client.cfg, k)
        }
        assert differ == {"use_mxu_tables", "fused_effects", "seg_effects"}
        assert not any(getattr(plain.client.cfg, k) for k in differ)
        assert (served.client._pipeline_depth, plain.client._pipeline_depth) == (4, 0)
        assert {k: v for k, v in served.config.items() if k not in ("engine", "client")} == {
            k: v for k, v in plain.config.items() if k not in ("engine", "client")
        }
        for a, b in zip(served.pool, plain.pool, strict=True):
            assert all(np.array_equal(x, y) for x, y in zip(a, b, strict=True))
    finally:
        served.client.stop()
        plain.client.stop()
