"""Blessed SPMD entry points and configs for the tier-4 analyzer.

Two consumers, two process roles:

* the WORKER (worker.py, forced 8-device CPU subprocess) lowers and
  compiles :func:`sharded_jobs` — the real entry points jitted with the
  shardings ``parallel/spmd.py`` declares — and reports the partitioned
  HLO's collectives and jaxpr consts;
* the PARENT (runner/__init__) folds :func:`entry_placements` and
  :func:`config_cases` — declared PartitionSpecs × ``jax.eval_shape``'d
  state leaves — with NO mesh and NO compile: divisibility and byte math
  are pure shape arithmetic.

The shardings themselves are imported from ``parallel/spmd.py`` (never
restated), so what the analyzer blesses is exactly what the runtime
binds to a live mesh.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Tuple

from sentinel_tpu.analysis.spmd.framework import LeafPlacement
from sentinel_tpu.parallel.meshspec import mesh_spec

#: canonical shapes for the non-tick entries (divisible by the mesh
#: width; the tick entry's shapes come from its EngineConfig)
WINDOW_ROWS = 128
WINDOW_BATCH = 64
TOKEN_SLOTS = 16
TOKEN_BATCH = 32


def tick_config():
    """The analyzer's tick config: the sketch-salsa tier at CI scale.

    sketch_width=512 (not the jaxpr tier's 256): the salsa level bitmap
    packs 16 width-cells per word, so the sharded word axis is width/64 —
    512 is the smallest width whose bitmap still splits 8 ways.
    """
    from sentinel_tpu.core.config import small_engine_config

    return small_engine_config(sketch_stats=True, sketch_width=512, hotset_k=8)


def window_config():
    from sentinel_tpu.ops import window as W

    return W.WindowConfig(sample_count=10, window_ms=100)


def sketch_tier_1m_config():
    """The 1M-ruled-resource sketch-tier operating point — the config
    whose per-shard footprint the HBM budgeter projects."""
    from sentinel_tpu.core.config import EngineConfig

    return EngineConfig(
        max_resources=16368,
        max_nodes=16376,
        batch_size=2048,
        complete_batch_size=2048,
        enable_minute_window=False,  # the sketch carries the minute scale
        sketch_stats=True,
        sketch_salsa=True,
        sketch_depth=2,
        sketch_width=1 << 16,
        sketch_capacity=1 << 21,
        sketch_sample_count=60,
        sketch_window_ms=1000,
        hotset_k=64,
    )


# -- placement math (parent-safe: eval_shape only, no devices) ---------------


def _axis_of(entry) -> Optional[str]:
    """One PartitionSpec dimension entry -> mesh axis name (1-D mesh:
    multi-axis tuples collapse to their first name)."""
    if entry is None:
        return None
    if isinstance(entry, (list, tuple)):
        return str(entry[0]) if entry else None
    return str(entry)


def placements_from(specs_tree, shapes_tree) -> List[LeafPlacement]:
    """Fold a PartitionSpec pytree with a ShapeDtypeStruct pytree into
    flat per-leaf placements (the divisibility/budget passes' input)."""
    import jax
    from jax.sharding import PartitionSpec as PS

    spec = mesh_spec()
    shape_leaves, _ = jax.tree_util.tree_flatten_with_path(shapes_tree)
    spec_leaves = jax.tree_util.tree_flatten(
        specs_tree, is_leaf=lambda x: isinstance(x, PS)
    )[0]
    if len(shape_leaves) != len(spec_leaves):
        raise ValueError(
            f"spec tree has {len(spec_leaves)} leaves but state has "
            f"{len(shape_leaves)} — parallel/spmd.py specs out of date?"
        )
    out: List[LeafPlacement] = []
    for (path, leaf), ps in zip(shape_leaves, spec_leaves):
        shape = tuple(int(d) for d in leaf.shape)
        dims = tuple(
            _axis_of(ps[i]) if i < len(ps) else None for i in range(len(shape))
        )
        itemsize = leaf.dtype.itemsize
        global_elems = 1
        shard_elems = 1
        for d, a in zip(shape, dims):
            global_elems *= d
            # ceil-divide: an indivisible dim costs the padded shard
            shard_elems *= -(-d // spec.n_devices) if a == spec.axis else d
        out.append(
            LeafPlacement(
                name=jax.tree_util.keystr(path),
                dtype=leaf.dtype.name,
                shape=shape,
                spec=dims,
                global_bytes=global_elems * itemsize,
                shard_bytes=shard_elems * itemsize,
            )
        )
    return out


def _tick_state_placements(cfg) -> List[LeafPlacement]:
    import jax

    from sentinel_tpu.ops import engine as E
    from sentinel_tpu.parallel import spmd

    shapes = jax.eval_shape(lambda: E._init_state(cfg))
    return placements_from(spmd.state_partition_specs(cfg), shapes)


def _window_state_placements(rows: int) -> List[LeafPlacement]:
    import jax

    from sentinel_tpu.ops import window as W
    from sentinel_tpu.parallel import spmd

    wcfg = window_config()
    shapes = jax.eval_shape(lambda: W.init_window(rows, wcfg))
    return placements_from(spmd.window_partition_specs(True), shapes)


def _token_col_state_placements(slots: int) -> List[LeafPlacement]:
    import jax

    from sentinel_tpu.ops import token_col as TC
    from sentinel_tpu.parallel import spmd

    shapes = jax.eval_shape(lambda: TC.init_state(slots))
    return placements_from(spmd.token_col_partition_specs(), shapes)


def entry_placements() -> Dict[str, List[LeafPlacement]]:
    """Declared per-leaf placements for each lowered entry's state."""
    return {
        "tick/sketch-salsa": _tick_state_placements(tick_config()),
        "window/add-batch": _window_state_placements(WINDOW_ROWS),
        "cluster/token-col": _token_col_state_placements(TOKEN_SLOTS),
    }


#: name of the ConfigCase the shard-hbm-budget pass projects
BUDGET_CONFIG = "bench/sketch-1m"


def config_cases() -> List[Tuple[str, List[LeafPlacement]]]:
    """(name, placements) for every blessed config — the divisibility
    pass's no-tracing input; BUDGET_CONFIG doubles as the HBM case."""
    from sentinel_tpu.core.config import EngineConfig

    return [
        ("engine/default", _tick_state_placements(EngineConfig())),
        ("tick/sketch-salsa", _tick_state_placements(tick_config())),
        ("window/add-batch", _window_state_placements(WINDOW_ROWS)),
        ("cluster/token-col", _token_col_state_placements(TOKEN_SLOTS)),
        (BUDGET_CONFIG, _tick_state_placements(sketch_tier_1m_config())),
    ]


# -- sharded jobs (worker-side: requires the forced mesh) --------------------


def sharded_jobs() -> List[Tuple[str, Callable, Tuple[Any, ...]]]:
    """(name, jitted fn with in/out shardings, example args) per entry.

    Only callable under the forced n-device CPU topology (worker.py);
    the jits are built by the SAME constructors the runtime uses
    (``spmd.make_sharded_tick`` / ``spmd.bind_shardings``).
    """
    import functools

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as PS

    from sentinel_tpu.analysis.jaxpr.entrypoints import _mk_tick_inputs
    from sentinel_tpu.ops import token_col as TC
    from sentinel_tpu.ops import window as W
    from sentinel_tpu.parallel import spmd

    spec = mesh_spec()
    mesh = spmd.make_mesh(spec.n_devices)
    rep = NamedSharding(mesh, PS())
    jobs: List[Tuple[str, Callable, Tuple[Any, ...]]] = []

    # 1. the engine tick, sketch-salsa tier — the runtime's own jit
    cfg = tick_config()
    jobs.append(
        (
            "tick/sketch-salsa",
            spmd.make_sharded_tick(cfg, mesh, donate=False),
            _mk_tick_inputs(cfg),
        )
    )

    # 2. the window scatter kernel, rows sharded
    wcfg = window_config()
    win_sh = spmd.bind_shardings(spmd.window_partition_specs(True), mesh)
    w_args = (
        W.init_window(WINDOW_ROWS, wcfg),
        jnp.int32(1_000),
        jnp.zeros((WINDOW_BATCH,), dtype=jnp.int32),
        jnp.zeros((WINDOW_BATCH, W.NUM_EVENTS), dtype=jnp.int32),
        jnp.zeros((WINDOW_BATCH,), dtype=jnp.float32),
    )
    jobs.append(
        (
            "window/add-batch",
            jax.jit(
                functools.partial(W.add_batch, cfg=wcfg),
                in_shardings=(win_sh, rep, rep, rep, rep),
                out_shardings=win_sh,
            ),
            w_args,
        )
    )

    # 3. the cluster token-column decision kernel, flow slots sharded
    tc_sh = spmd.bind_shardings(spmd.token_col_partition_specs(), mesh)
    t_args = (
        TC.init_state(TOKEN_SLOTS),
        jnp.int32(1_000),
        jnp.zeros((TOKEN_BATCH,), dtype=jnp.int32),
        jnp.ones((TOKEN_BATCH,), dtype=jnp.int32),
        jnp.zeros((TOKEN_BATCH,), dtype=jnp.int32),
        jnp.zeros((TOKEN_BATCH,), dtype=bool),
        jnp.zeros((TOKEN_BATCH,), dtype=bool),
    )
    jobs.append(
        (
            "cluster/token-col",
            jax.jit(
                functools.partial(TC.decide_batch, cfg=TC.DEFAULT_CFG),
                in_shardings=(tc_sh, rep, rep, rep, rep, rep, rep),
                out_shardings=(rep, rep, tc_sh),
            ),
            t_args,
        )
    )
    return jobs
