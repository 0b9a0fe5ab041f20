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


# -- the breaker and window planes at 107,008 rows (PR 39) ------------------

_LAID = re.compile(r"(\w+)\[([\d,]+)\]\{([\d,]+):T\(([\d,]+)\)")


def _padded_bytes(dtype, dims, minor_to_major, tile, width=4):
    """Bytes an array takes as the chip lays it out: its tile covers the
    minor-most dimensions, each rounded up to the tile's."""
    dims = [int(n) for n in dims.split(",")]
    order = [int(n) for n in minor_to_major.split(",")]
    tile = [int(n) for n in tile.split(",")]
    for axis, t in zip(order, reversed(tile)):
        dims[axis] = -(-dims[axis] // t) * t
    return math.prod(dims) * (1 if dtype == "pred" else width)


@pytest.fixture(scope="module")
def breaker_tick(one_chip):
    """The light tick of perfbench/configs/degrade-100k-slow-ratio.json's
    engine sizes (106,992 resources and as many breakers, no sketch tier, one
    breaker bucket) with the degrade stage, as text and memory analysis: about
    50 s of the chip's compiler, once for the tests below."""
    import json
    import os

    from sentinel_tpu.core.config import EngineConfig
    from sentinel_tpu.ops import engine as E
    from sentinel_tpu.ops import wire as WIRE
    from sentinel_tpu.runtime.registry import Registry

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "perfbench", "configs", "degrade-100k-slow-ratio.json")) as f:
        sizes = json.load(f)["engine"]
    cfg = EngineConfig(**sizes, use_mxu_tables=True, fused_effects=True, seg_effects=True,
                       packed_wire=True)

    def described(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(jnp.shape(x), x.dtype, sharding=one_chip), tree)

    state = described(jax.eval_shape(lambda: E._init_state(cfg)))
    rules = described(E.compile_ruleset(cfg, Registry(cfg)))
    b, b2 = WIRE.tick_shapes(cfg)[0]
    wire = jax.ShapeDtypeStruct((WIRE.input_layout_for(cfg, b, b2).total,), jnp.uint32,
                                sharding=one_chip)
    tick = E.make_tick(cfg, features=frozenset({"nodes", "occupy", "flow", "degrade"}), wire_in=True)
    with mock.patch.object(FU, "interpret_mode", lambda: False):  # the backend here is the CPU
        compiled = tick.lower(state, rules, wire).compile()
    return cfg, compiled.as_text(), compiled.memory_analysis()


def _entry_planes(text, prefix):
    """``{name: (dtype, dims, minor_to_major, tile)}`` of the tick's state
    arguments whose name starts with ``prefix``."""
    found = {}
    for line in text.splitlines():
        m = re.search(rf"%state_({prefix}\w*?)\.\d+ = " + _LAID.pattern + r".* parameter\(", line)
        if m:
            found[m.group(1)] = m.groups()[1:]
    return found


def test_the_breaker_planes_padding_stays_within_a_small_multiple_of_their_data(breaker_tick):
    """``cb_counts`` [rule, bucket, 3] and ``cb_epochs`` [rule, bucket] at
    100,000 rules: ISSUE 39 reckoned a (bucket, 3) face tiled 8 x 128 a rule,
    4 KiB each and 400 MB in all.  The chip's compiler does not lay them out
    so: it puts the rule axis minor-most in every breaker plane it is handed,
    and the four planes take a few MB.  Held here so that a change of the
    planes' form that loses that shows."""
    cfg, text, _memory = breaker_tick
    rules = cfg.max_degrade_rules + 1
    planes = _entry_planes(text, "cb_")
    assert set(planes) == {"cb_state", "cb_retry_ms", "cb_counts", "cb_epochs"}
    for name, (dtype, dims, order, tile) in planes.items():
        sizes = [int(n) for n in dims.split(",")]
        assert sizes[int(order.split(",")[0])] == rules, (name, dims, order)  # rules on the lanes
        data = math.prod(sizes) * 4
        assert _padded_bytes(dtype, dims, order, tile) <= 2.5 * data, (name, dims, order, tile)


def test_the_window_planes_padding_stays_within_twice_their_data(breaker_tick):
    """The second and the minute window at 107,008 rows: the compiler keeps
    the row axis minor-most in every plane it is handed (a minute plane's 60
    buckets round up to 64), so the state's arguments take at most twice
    their data.  Whole-plane copies ARE in this program (a window plane that
    a ``lax.cond`` branch updates is laid out anew at the branch's edge:
    PERF.md sections 5 and 7, PR 39, price them on the chip); the change that
    takes them out brings the assertion that none comes back."""
    cfg, text, memory = breaker_tick
    planes = _entry_planes(text, "win_")
    assert {"win_sec_counts", "win_min_counts", "win_min_rt_sum", "win_sec_run"} <= set(planes)
    data = padded = 0
    for name, (dtype, dims, order, tile) in planes.items():
        n = math.prod(int(x) for x in dims.split(","))
        if n < cfg.node_rows:
            continue
        assert order.split(",")[0] == "0", (name, order)  # the row axis on the lanes
        data += n * 4
        padded += _padded_bytes(dtype, dims, order, tile)
    assert data > 150e6 and padded <= 2.0 * data
    assert memory.argument_size_in_bytes <= 2.0 * data
