"""Segment-compacted effects (ops/engine_seg.py) vs the per-item fused
path: full-tick bit-identity, with and without capacity fallback.

Runs on CPU with Pallas interpret kernels — semantics only; device speed
is perfbench/run.py's job.
"""

from __future__ import annotations

import numpy as np
import jax
import pytest

from sentinel_tpu.core.config import small_engine_config
from tests.test_fused import _tick_once

# Full-tick multi-config equivalence: minutes per test on a 1-core host
# (eager pallas interpret kernels compile per distinct kernel plan, and
# the _respawned isolation pays a fresh interpreter + jax import each).
# Excluded from the tier-1 gate (-m 'not slow'); run explicitly before
# touching the seg engine:  pytest tests/test_engine_seg.py -m ''
pytestmark = pytest.mark.slow

_BASELINE_CACHE: dict = {}


def _respawned(test_id: str) -> bool:
    """Run ``test_id`` in a FRESH interpreter and return True in the
    parent (the caller then returns immediately; the child re-enters with
    SENTINEL_SUBTEST=1 and runs the real body).

    Why: this jaxlib's CPU backend segfaults inside
    backend_compile_and_load once a single process has accumulated enough
    large engine compiles (reproduced repeatedly at the suite's ~20th
    engine compile, independent of wall clock, stack size, or system
    load; any single test passes alone).  Isolating the heavy NEW seg
    configs keeps the per-process compile count at the level the rest of
    the suite was built for — same compiler fragility the conftest's
    compilation-cache note records."""
    import os
    import subprocess
    import sys

    if os.environ.get("SENTINEL_SUBTEST") == "1":
        return False
    env = dict(os.environ, SENTINEL_SUBTEST="1")
    r = subprocess.run(
        [
            sys.executable, "-m", "pytest", "-x", "-q",
            # -o addopts= strips pytest.ini's xdist options (-n 4): each
            # respawn must be ONE plain in-process session, not a 4-worker
            # xdist fleet of its own; no:cacheprovider keeps respawns from
            # racing on .pytest_cache
            "-p", "no:cacheprovider", "-o", "addopts=", test_id,
        ],
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=env,
        capture_output=True,
        text=True,
        timeout=1800,
    )
    assert r.returncode == 0, (
        f"subtest failed:\n{r.stdout[-3000:]}\n{r.stderr[-2000:]}"
    )
    return True


def _baseline(sketch: bool, base: dict):
    if sketch not in _BASELINE_CACHE:
        _BASELINE_CACHE[sketch] = _tick_once(small_engine_config(**base))
    return _BASELINE_CACHE[sketch]


def _assert_state_equal(st1, st2):
    l1 = jax.tree.leaves(st1)
    l2 = jax.tree.leaves(st2)
    paths = [str(p) for p, _ in jax.tree_util.tree_flatten_with_path(st1)[0]]
    for p, x, y in zip(paths, l1, l2):
        np.testing.assert_array_equal(x, y, err_msg=p)


@pytest.mark.parametrize(
    "sketch,seg_u", [(False, 0), (True, 0), (False, 16)]
)
def test_seg_tick_matches_fused_path(sketch, seg_u):
    """seg_u=0: auto capacity (compacted path taken).  seg_u=16: capacity
    too small for the unsorted 96-item batch -> every tick falls back to
    the per-item kernels.  Both must match the plain fused path exactly."""
    base = dict(
        batch_size=96,
        complete_batch_size=96,
        use_mxu_tables=True,
        sketch_stats=sketch,
        enable_minute_window=True,
        fused_effects=True,
    )
    cfg_seg = small_engine_config(**base, seg_effects=True, seg_u=seg_u)
    st1, out1 = _baseline(sketch, base)
    st2, out2 = _tick_once(cfg_seg)
    for a, b in zip(out1, out2):
        np.testing.assert_array_equal(a, b)
    _assert_state_equal(st1, st2)


@pytest.mark.parametrize("sort_batches", [True, False])
def test_seg_no_fallback_matches_when_capacity_fits(sort_batches):
    """seg_fallback=False removes the check-phase lax.cond entirely; with
    capacity that fits (auto seg_u), verdicts and state must still be
    bit-identical to the always-exact seg_fallback=True engine.

    Fresh-interpreter isolated: see _respawned."""
    if _respawned(
        f"{__file__}::test_seg_no_fallback_matches_when_capacity_fits"
        f"[{sort_batches}]"
    ):
        return
    base = dict(
        batch_size=96,
        complete_batch_size=96,
        use_mxu_tables=True,
        enable_minute_window=True,
        fused_effects=True,
        flow_rules_per_resource=1,
        degrade_rules_per_resource=1,
        param_rules_per_resource=1,
    )
    # unsorted batches make ~B segments; cover them so nothing overflows
    cfg_a = small_engine_config(**base, seg_effects=True, seg_u=128)
    cfg_b = small_engine_config(
        **base, seg_effects=True, seg_u=128, seg_fallback=False
    )
    st1, out1 = _tick_once(cfg_a, sort_batches=sort_batches)
    st2, out2 = _tick_once(cfg_b, sort_batches=sort_batches)
    for a, b in zip(out1, out2):
        np.testing.assert_array_equal(a, b)
    _assert_state_equal(st1, st2)
