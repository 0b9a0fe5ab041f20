"""Kernels of the served path compiled at their real widths for a described
v5e chip (no chip attached: nothing runs, so nothing here is a time or a
result).  Interpret mode cannot show what the chip's compiler refuses: a
slice off the tiling, a table past a kernel's fast memory.

One file, and the topology described inside a fixture: only one process may
hold the TPU's library, so only the worker that is given this file loads it;
where it cannot be described the tests skip."""

import functools
import math
import re
from unittest import mock

import jax
import jax.numpy as jnp
import pytest

from sentinel_tpu.ops import fused as FU

pytestmark = pytest.mark.jitted


@pytest.fixture(autouse=True)
def _whole_locations():
    """A kernel's name reaches the compiled text through its operation's
    location, whole.  ``perfbench/run.py``'s set-up cuts locations to one
    frame for the process (its compile-cache key) and leaves them cut: a
    rehearsal that ran earlier in this worker would take the names away."""
    was = jax.config.jax_include_full_tracebacks_in_locations
    jax.config.update("jax_include_full_tracebacks_in_locations", True)
    yield
    jax.config.update("jax_include_full_tracebacks_in_locations", was)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("rows", [256, 32768])
def test_scatter_sorted_compiles_at_the_gateway_stores_width(one_chip, rows):
    """The hot-parameter store of perfbench/configs/param-1m-hot-keys.json
    (2^22 cells a depth, the widest EngineConfig admits) at the light and
    the middle tick shape: a count plane and a concurrency plane, one digit
    each, as the tick's ``param{d}`` jobs have them."""
    from sentinel_tpu.core.config import PARAM_MAX_WIDTH

    def write(r, v):
        return FU.scatter_sorted(FU.Job("param0", PARAM_MAX_WIDTH, r, v, (1, 1)), interpret=False)

    r = jax.ShapeDtypeStruct((1, rows), jnp.int32, sharding=one_chip)
    v = jax.ShapeDtypeStruct((1, 2, rows), jnp.int32, sharding=one_chip)
    compiled = jax.jit(write).lower(r, v).compile()
    assert "scatter_sorted" in compiled.as_text()


def _wide_cfg():
    from sentinel_tpu.core.config import PARAM_MAX_WIDTH, EngineConfig

    return EngineConfig(param_width=PARAM_MAX_WIDTH, use_mxu_tables=True, fused_effects=True)


def _i32(one_chip, shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)


def _relayouts(text, cells=1 << 22):
    """The compiled program's ``copy`` and ``transpose`` operations that
    produce an array of at least ``cells`` elements, one plane of the widest
    store (an asynchronous ``copy-start`` moves an operand between memories
    as it lies and is none)."""
    made = re.compile(r"= \w+\[([\d,]+)\]\S* (?:copy|transpose)\(")
    found = []
    for line in text.splitlines():
        m = made.search(line)
        if m and math.prod(int(n) for n in m.group(1).split(",")) >= cells:
            found.append(line.strip()[:160])
    return found


def test_a_wide_stores_refresh_and_landing_update_it_in_place(one_chip):
    """``P.refresh`` then ``P.land`` on a donated store of 2^22 cells a depth
    (256 MiB): the compiler keeps the tiled form as it is given
    (``{3,2,1,0:T(8,128)}``: a bucket's row is whole tiles), copies the store
    nowhere and needs next to no temporaries.  As [depth, bucket, cell] it
    chose another layout for the store and copied it in and out (two copies,
    268,532,224 bytes of temporaries; PERF.md section 6, PR 35)."""
    from sentinel_tpu.ops import param as P

    cfg = _wide_cfg()

    def refresh_land(pcms, epochs, now_ms, upd):
        pcms, epochs, idx = P.refresh(pcms, epochs, now_ms, cfg)
        return P.land(cfg, pcms, idx, upd), epochs

    s = functools.partial(_i32, one_chip)
    assert P.store_shape(cfg) == (2, 8, 32768, 128) and P.conc_shape(cfg) == (2, 32768, 128)
    compiled = jax.jit(refresh_land, donate_argnums=(0,)).lower(
        s(P.store_shape(cfg)), s((cfg.param_sample_count,)), s(()), s(P.conc_shape(cfg))
    ).compile()
    text = compiled.as_text()
    assert "s32[2,8,32768,128]{3,2,1,0:T(8,128)} parameter(0)" in text
    assert not _relayouts(text)
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


def test_a_wide_stores_hand_back_lays_no_plane_out_anew(one_chip):
    """From ``scatter_sorted``'s output to the store: each depth's ``param{d}``
    job through ``engine._param_tiles`` and ``_param_upd``, the counts landed
    by ``P.land`` and the concurrency added to ``pconc``, with no ``copy`` or
    ``transpose`` of a [.., 32768, 128] plane or of the store (through [Q, 2]
    and [depth, Q, 2] the compiler laid whole planes out anew five times)."""
    from sentinel_tpu.ops import engine as E
    from sentinel_tpu.ops import param as P

    cfg = _wide_cfg()
    rows = 32768

    def hand_back(r, v, pcms, pconc, idx):
        jobs = [FU.Job(f"param{d}", cfg.param_width, r[d], v, (1, 1)) for d in range(cfg.param_depth)]
        with mock.patch.object(FU, "interpret_mode", lambda: False):  # the backend here is the CPU
            counts, conc = E._param_upd(cfg, E._param_tiles(jobs))
        return P.land(cfg, pcms, idx, counts), jnp.maximum(pconc + conc, 0)

    s = functools.partial(_i32, one_chip)
    compiled = jax.jit(hand_back, donate_argnums=(2, 3)).lower(
        s((cfg.param_depth, 1, rows)), s((1, 2, rows)), s(P.store_shape(cfg)), s(P.conc_shape(cfg)), s(())
    ).compile()
    text = compiled.as_text()
    assert text.count("scatter_sorted") >= cfg.param_depth
    assert not _relayouts(text)
    # the kernels' own tables, two planes a depth, and nothing the size of one beside them
    assert compiled.memory_analysis().temp_size_in_bytes < (2 * 2 + 1) * 32768 * 128 * 4
