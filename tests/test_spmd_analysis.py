"""sentinel_tpu.analysis.spmd — the tier-4 SPMD/sharding analyzer.

Three jobs, mirroring the tier-3 suite:

1. unit-test every pass on synthetic :class:`SpmdProgram` fixtures — one
   triggering and one clean per rule (a NEW collective vs the golden, a
   full-leaf and a slice-of-sharded-dim all-gather, an oversized
   replicated const/leaf, an indivisible sharded dim, an over-budget
   shard), plus HLO parsing, the golden round-trip, and the scoped
   ``--update-baseline`` contract;
2. THE CI GATE: run the whole tier against the real repo — zero
   findings, the committed ``collectives.json`` must exactly match the
   worker's current inventory, and the projected 1M-resource per-shard
   footprint must clear the HBM capacity SLO;
3. topology hygiene: lowering under the forced 8-device mesh happens in
   a SUBPROCESS, so the calling process's jax device count must be
   byte-for-byte unchanged after a full tier-4 run.

The fixture tests are pure plain-data work (no jax); the gate pays one
worker subprocess (~10 s, cached per process) shared across tests.
"""

from __future__ import annotations

import json
import os
import textwrap

import pytest

from sentinel_tpu.analysis import REPO_ROOT, rule_catalog
from sentinel_tpu.analysis.framework import format_sarif
from sentinel_tpu.analysis.spmd import (
    COLLECTIVES_PATH,
    build_program,
    capacity_slo_bytes,
    run_spmd_analysis,
    update_collectives,
)
from sentinel_tpu.analysis.spmd.framework import (
    Collective,
    ConfigCase,
    ConstInfo,
    LeafPlacement,
    ShardedEntry,
    SpmdProgram,
    group_collectives,
    ledger_bytes,
    parse_hlo_collectives,
)
from sentinel_tpu.analysis.spmd.passes import (
    ALL_SPMD_PASSES,
    CollectiveLedgerPass,
    ImplicitReshardPass,
    ReplicationHazardPass,
    ShardDivisibilityPass,
    ShardHbmBudgetPass,
)
from sentinel_tpu.parallel.meshspec import force_cpu_mesh_env, mesh_spec

N = mesh_spec().n_devices


def _leaf(name, shape, spec, itemsize=4, dtype="float32"):
    """LeafPlacement with the byte math the real fold performs."""
    g = itemsize
    s = itemsize
    for d, a in zip(shape, spec):
        g *= d
        s *= -(-d // N) if a is not None else d
    return LeafPlacement(
        name=name, dtype=dtype, shape=tuple(shape), spec=tuple(spec),
        global_bytes=g, shard_bytes=s,
    )


def _prog(**kw):
    kw.setdefault("n_devices", N)
    kw.setdefault("axis", mesh_spec().axis)
    return SpmdProgram(**kw)


def _golden_for(*entries):
    """A golden dict that exactly pins the given entries' inventories."""
    out = {}
    for e in entries:
        groups = group_collectives(e.collectives)
        out[e.name] = {
            "collectives": groups,
            "bytes_per_tick": ledger_bytes(groups),
        }
    return {"entries": out}


def _run(p, program):
    return list(p.run(program))


# ---------------------------------------------------------------------------
# HLO parsing
# ---------------------------------------------------------------------------

_HLO = textwrap.dedent(
    """\
    HloModule jit_tick, is_scheduled=true

    FileNames
    1 "@ROOT@/sentinel_tpu/ops/tables.py"
    2 "/somewhere/else/x.py"

    FunctionNames
    1 "depth_gather_1col"
    2 "elsewhere"

    FileLocations
    1 {file_name_id=1 function_name_id=1 line=255 end_line=255 column=15 end_column=44}
    2 {file_name_id=2 function_name_id=2 line=3 end_line=3 column=1 end_column=9}

    StackFrames
    1 {file_location_id=2 parent_frame_id=1}
    2 {file_location_id=1 parent_frame_id=2}

    ENTRY %main {
    %all-gather.1 = s32[2,512]{1,0} all-gather(s32[2,64]{1,0} %p), dimensions={1}, metadata={op_name="x" stack_frame_id=2}
    %ar = f32[63]{0} all-reduce(f32[63]{0} %q), to_apply=%add
    %ag2 = s32[2,512]{1,0} all-gather-start(s32[2,64]{1,0} %r), dimensions={1}
    %cp = s32[7,5]{1,0} collective-permute(s32[7,5]{1,0} %s), source_target_pairs={{0,1}}
    %elsewhere = f32[8]{0} all-reduce(f32[8]{0} %t), metadata={op_name="y" stack_frame_id=1}
    %combined = (f32[1]{0}, s32[63,5]{1,0}) all-reduce(f32[1]{0} %u, s32[63,5]{1,0} %v), to_apply=%add
    %gte = f32[1]{0} get-tuple-element((f32[1]{0}, s32[63,5]{1,0}) %combined), index=0
    }
    """
).replace("@ROOT@", REPO_ROOT)


def test_parse_hlo_collectives_kinds_shapes_and_sources():
    colls = parse_hlo_collectives(_HLO, REPO_ROOT)
    assert [(c.kind, c.dtype, c.shape) for c in colls] == [
        ("all-gather", "s32", (2, 512)),
        ("all-reduce", "f32", (63,)),
        ("all-gather", "s32", (2, 512)),  # -start folds into the base kind
        ("collective-permute", "s32", (7, 5)),
        ("all-reduce", "f32", (8,)),
        # a combiner-merged tuple op counts once per element
        ("all-reduce", "f32", (1,)),
        ("all-reduce", "s32", (63, 5)),
    ]
    # in-repo source metadata is relativized; out-of-repo dropped
    assert colls[0].source == "sentinel_tpu/ops/tables.py"
    assert colls[0].line == 255
    assert colls[4].source is None
    assert colls[0].nbytes == 2 * 512 * 4


def test_group_collectives_merges_and_ignores_source_lines():
    a = Collective("all-gather", "s32", (2, 512), "f.py", 10)
    b = Collective("all-gather", "s32", (2, 512), "g.py", 99)
    groups = group_collectives([a, b])
    assert len(groups) == 1
    assert groups[0]["count"] == 2
    assert groups[0]["bytes_each"] == 4096
    assert ledger_bytes(groups) == 8192


# ---------------------------------------------------------------------------
# collective-ledger
# ---------------------------------------------------------------------------


def _entry(name="tick/fix", colls=()):
    return ShardedEntry(name=name, collectives=list(colls))


def test_ledger_clean_when_inventory_matches_golden():
    e = _entry(colls=[Collective("all-gather", "s32", (2, 512))] * 2)
    prog = _prog(entries=[e], golden=_golden_for(e))
    assert _run(CollectiveLedgerPass(), prog) == []


def test_ledger_new_collective_is_error():
    e = _entry(colls=[Collective("all-gather", "s32", (2, 512))])
    golden = _golden_for(_entry(colls=[]))
    prog = _prog(entries=[e], golden=golden)
    found = _run(CollectiveLedgerPass(), prog)
    new = [f for f in found if "NEW collective" in f.message]
    assert len(new) == 1
    f = new[0]
    assert f.rule == "collective-ledger" and f.severity == "error"
    assert f.path == "spmd://tick/fix"
    assert "all-gather" in f.message
    # the added bytes also blow the pinned total — both angles report
    assert any("bytes/tick" in f.message for f in found)


def test_ledger_count_growth_is_error():
    pinned = _entry(colls=[Collective("all-reduce", "f32", (63,))])
    cur = _entry(colls=[Collective("all-reduce", "f32", (63,))] * 3)
    prog = _prog(entries=[cur], golden=_golden_for(pinned))
    found = _run(CollectiveLedgerPass(), prog)
    # count growth AND the byte total blowing past tolerance
    assert any("count grew 1 -> 3" in f.message for f in found)


def test_ledger_bytes_regression_past_tolerance():
    e = _entry(colls=[Collective("all-gather", "s32", (2, 512))] * 2)
    golden = _golden_for(e)
    # same inventory, but the pinned byte total was smaller: regression
    golden["entries"]["tick/fix"]["bytes_per_tick"] = 1000
    found = _run(CollectiveLedgerPass(), _prog(entries=[e], golden=golden))
    assert len(found) == 1
    assert "bytes/tick" in found[0].message and "ceiling" in found[0].message


def test_ledger_within_tolerance_is_clean():
    e = _entry(colls=[Collective("all-gather", "s32", (2, 512))] * 2)
    golden = _golden_for(e)
    golden["entries"]["tick/fix"]["bytes_per_tick"] = 8000  # 8192 < 8000*1.25
    assert _run(CollectiveLedgerPass(), _prog(entries=[e], golden=golden)) == []


def test_ledger_stale_golden_entry_and_unpinned_entry():
    e = _entry(name="tick/live", colls=[])
    golden = _golden_for(_entry(name="tick/gone", colls=[]))
    found = _run(CollectiveLedgerPass(), _prog(entries=[e], golden=golden))
    msgs = "\n".join(f.message for f in found)
    assert "no pinned collective ledger" in msgs  # tick/live unpinned
    assert "stale pin" in msgs  # tick/gone no longer lowered
    paths = {f.path for f in found}
    assert "spmd://tick/gone" in paths


def test_ledger_missing_golden_is_one_loud_error():
    prog = _prog(entries=[_entry()], golden=None)
    found = _run(CollectiveLedgerPass(), prog)
    assert len(found) == 1
    assert "--update-collectives" in found[0].message


def test_worker_error_surfaces_once_and_quiets_hlo_passes():
    prog = _prog(worker_error="boom: exit 3", golden={"entries": {}})
    found = _run(CollectiveLedgerPass(), prog)
    assert len(found) == 1 and "boom" in found[0].message
    assert found[0].path == "spmd://analyzer"
    assert _run(ImplicitReshardPass(), prog) == []
    # the placement passes still run (they need no HLO)
    assert _run(ShardDivisibilityPass(), prog) == []


# ---------------------------------------------------------------------------
# implicit-reshard
# ---------------------------------------------------------------------------


def test_reshard_full_leaf_rematerialization():
    leaf = _leaf(".tab", (8, 512), (None, "res"))  # 16 KiB global
    e = ShardedEntry(
        name="tick/fix",
        collectives=[
            Collective("all-gather", "f32", (8, 512), "sentinel_tpu/x.py", 7)
        ],
        placements=[leaf],
    )
    found = _run(ImplicitReshardPass(), _prog(entries=[e]))
    assert len(found) == 1
    f = found[0]
    assert f.path == "sentinel_tpu/x.py" and f.line == 7
    assert "re-materializes the full sharded leaf .tab" in f.message


def test_reshard_slice_of_sharded_dim_is_caught():
    """The salsa-read class: the gather result is only a SLICE of the
    leaf, but it spans the sharded dimension at global size."""
    leaf = _leaf(".gs.run", (2, 8, 512), (None, None, "res"), dtype="int32")
    e = ShardedEntry(
        name="tick/fix",
        collectives=[
            Collective("all-gather", "s32", (2, 512), "sentinel_tpu/y.py", 9)
        ],
        placements=[leaf],
    )
    found = _run(ImplicitReshardPass(), _prog(entries=[e]))
    assert len(found) == 1
    f = found[0]
    assert f.path == "sentinel_tpu/y.py" and f.line == 9
    assert "full sharded dimension of .gs.run" in f.message


def test_reshard_small_gather_and_nonmatching_dims_are_clean():
    leaf = _leaf(".gs.run", (2, 8, 512), (None, None, "res"), dtype="int32")
    e = ShardedEntry(
        name="tick/fix",
        collectives=[
            # 256 B: below the match floor even though 64 is a real dim
            Collective("all-gather", "s32", (64,), "sentinel_tpu/z.py", 1),
            # large dims but none is a sharded-dim size: no slice match
            Collective("all-gather", "s32", (3, 100)),
        ],
        placements=[leaf],
    )
    assert _run(ImplicitReshardPass(), _prog(entries=[e])) == []


def test_reshard_big_unmatched_gather_is_flagged():
    e = ShardedEntry(
        name="tick/fix",
        collectives=[Collective("all-gather", "f32", (1 << 16,))],  # 256 KiB
    )
    found = _run(ImplicitReshardPass(), _prog(entries=[e]))
    assert len(found) == 1
    assert "large all-gather" in found[0].message
    assert found[0].path == "spmd://tick/fix"  # no source metadata


# ---------------------------------------------------------------------------
# replication-hazard
# ---------------------------------------------------------------------------


def test_replicated_const_over_threshold_is_error():
    e = ShardedEntry(
        name="tick/fix",
        consts=[ConstInfo("f32", (512, 512), 512 * 512 * 4)],  # 1 MiB
    )
    found = _run(ReplicationHazardPass(), _prog(entries=[e]))
    assert len(found) == 1
    assert "jaxpr const" in found[0].message
    assert found[0].path == "spmd://tick/fix"


def test_small_const_and_sharded_big_leaf_are_clean():
    e = ShardedEntry(name="tick/fix", consts=[ConstInfo("f32", (64,), 256)])
    big_but_sharded = _leaf(".win.counts", (1 << 22, 4), ("res", None))
    case = ConfigCase(name="bench/big", placements=[big_but_sharded])
    prog = _prog(entries=[e], configs=[case])
    assert _run(ReplicationHazardPass(), prog) == []


def test_replicated_big_leaf_at_config_scale_is_error():
    lazy = _leaf(".gs.words", (4, 1 << 21), (None, None))  # 32 MiB replicated
    case = ConfigCase(name="bench/sketch-1m", placements=[lazy])
    found = _run(ReplicationHazardPass(), _prog(configs=[case]))
    assert len(found) == 1
    f = found[0]
    assert f.path == "spmd://config/bench/sketch-1m"
    assert ".gs.words" in f.message and "replicated" in f.message


# ---------------------------------------------------------------------------
# shard-divisibility
# ---------------------------------------------------------------------------


def test_indivisible_sharded_dim_is_error():
    bad = _leaf(".win.counts", (137, 4), ("res", None))
    case = ConfigCase(name="engine/odd", placements=[bad])
    found = _run(ShardDivisibilityPass(), _prog(configs=[case]))
    assert len(found) == 1
    f = found[0]
    assert f.rule == "shard-divisibility"
    assert "137" in f.message and f"{N}-device" in f.message


def test_divisible_and_replicated_dims_are_clean():
    case = ConfigCase(
        name="engine/even",
        placements=[
            _leaf(".a", (136, 4), ("res", None)),
            _leaf(".b", (137, 4), (None, None)),  # odd but replicated
        ],
    )
    assert _run(ShardDivisibilityPass(), _prog(configs=[case])) == []


# ---------------------------------------------------------------------------
# shard-hbm-budget
# ---------------------------------------------------------------------------


def test_budget_overflow_names_the_heaviest_leaves():
    case = ConfigCase(
        name="bench/sketch-1m",
        placements=[
            _leaf(".big", (1 << 20, 8), ("res", None)),  # 4 MiB/shard
            _leaf(".small", (64,), (None,)),
        ],
    )
    prog = _prog(
        configs=[case], budget_config="bench/sketch-1m",
        capacity_bytes=1 << 20,
    )
    found = _run(ShardHbmBudgetPass(), prog)
    assert len(found) == 1
    f = found[0]
    assert f.path == "spmd://config/bench/sketch-1m"
    assert ".big" in f.message and "capacity SLO" in f.message


def test_budget_under_capacity_is_clean_and_missing_case_is_loud():
    case = ConfigCase(
        name="bench/sketch-1m",
        placements=[_leaf(".t", (1024,), ("res",))],
    )
    ok = _prog(
        configs=[case], budget_config="bench/sketch-1m",
        capacity_bytes=1 << 30,
    )
    assert _run(ShardHbmBudgetPass(), ok) == []
    wired_wrong = _prog(configs=[], budget_config="bench/sketch-1m",
                        capacity_bytes=1 << 30)
    found = _run(ShardHbmBudgetPass(), wired_wrong)
    assert len(found) == 1 and "wiring" in found[0].message


# ---------------------------------------------------------------------------
# meshspec: the one shared topology contract
# ---------------------------------------------------------------------------


def test_force_cpu_mesh_env_fresh_environment():
    env = {}
    n = force_cpu_mesh_env(env)
    assert n == N
    assert env["JAX_PLATFORMS"] == "cpu"
    assert f"--xla_force_host_platform_device_count={N}" in env["XLA_FLAGS"]


def test_force_cpu_mesh_env_keep_existing_count():
    env = {"XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    n = force_cpu_mesh_env(env, keep_existing_count=True)
    assert n == 4
    assert "device_count=4" in env["XLA_FLAGS"]
    # without the keep flag the blessed width wins (and dupes collapse)
    n2 = force_cpu_mesh_env(env)
    assert n2 == N
    assert env["XLA_FLAGS"].count("device_count") == 1


def test_runtime_mesh_axis_comes_from_meshspec():
    """Every axis any runtime PartitionSpec names IS the meshspec axis —
    the analyzer and the runtime cannot shard on different names."""
    import jax
    from jax.sharding import PartitionSpec as PS

    from sentinel_tpu.core.config import EngineConfig
    from sentinel_tpu.parallel import spmd

    specs = spmd.state_partition_specs(EngineConfig())
    leaves = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, PS)
    )
    axes = {a for ps in leaves for a in ps if a is not None}
    assert axes == {mesh_spec().axis}


# ---------------------------------------------------------------------------
# golden round-trip + scoped baseline update
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_update_collectives_round_trip(tmp_path):
    """--update-collectives writes a reviewable golden that a fresh
    build_program round-trips to zero ledger findings."""
    path = str(tmp_path / "collectives.json")
    n = update_collectives(path)
    assert n == 3  # the three blessed entries
    data = json.loads(open(path).read())
    assert "--update-collectives" in data["comment"]
    assert data["mesh"] == {"axis": mesh_spec().axis, "n_devices": N}
    assert set(data["entries"]) == {
        "tick/sketch-salsa", "window/add-batch", "cluster/token-col",
    }
    prog = build_program(golden_path=path)
    assert _run(CollectiveLedgerPass(), prog) == []


def test_update_baseline_scoped_to_spmd_preserves_other_tiers(tmp_path):
    """--tier spmd --update-baseline must not evict other tiers' accepted
    debt: only spmd-owned entries are in scope for the rewrite."""
    from sentinel_tpu.analysis.__main__ import main

    path = tmp_path / "baseline.json"
    path.write_text(json.dumps({"accepted": {"fail-open:sentinel_tpu/foo.py": 2}}))
    rc = main(["--tier", "spmd", "--update-baseline", "--baseline", str(path)])
    assert rc == 0
    kept = json.loads(path.read_text())["accepted"]
    assert kept.get("fail-open:sentinel_tpu/foo.py") == 2
    # the tier itself is clean, so nothing spmd-owned was added
    spmd_rules = {p.name for p in ALL_SPMD_PASSES}
    assert [k for k in kept if k.split(":")[0] in spmd_rules] == []


# ---------------------------------------------------------------------------
# CLI / reporting integration
# ---------------------------------------------------------------------------


def test_rule_catalog_spans_four_tiers():
    cat = rule_catalog()
    for p in ALL_SPMD_PASSES:
        assert p.name in cat and cat[p.name]
    assert len(ALL_SPMD_PASSES) == 5


def test_sarif_spmd_pseudo_paths_claim_no_uri_base():
    e = ShardedEntry(
        name="tick/fix",
        collectives=[Collective("all-gather", "f32", (1 << 16,))],
    )
    case = ConfigCase(
        name="engine/odd",
        placements=[_leaf(".w", (137,), ("res",))],
    )
    prog = _prog(entries=[e], configs=[case], golden=None)
    findings = []
    for p in ALL_SPMD_PASSES:
        findings.extend(p.run(prog))
    assert findings
    doc = json.loads(format_sarif(findings, findings, rule_catalog()))
    run = doc["runs"][0]
    rule_ids = {r["id"] for r in run["tool"]["driver"]["rules"]}
    assert {"collective-ledger", "implicit-reshard", "shard-divisibility"} <= rule_ids
    locs = [
        r["locations"][0]["physicalLocation"]["artifactLocation"]
        for r in run["results"]
    ]
    pseudo = [l for l in locs if l["uri"].startswith("spmd://")]
    assert pseudo and all("uriBaseId" not in l for l in pseudo)


# ---------------------------------------------------------------------------
# THE repo gate
# ---------------------------------------------------------------------------


def test_repo_gate_zero_findings_golden_matches_and_budget_clears():
    """The CI contract for this tier: the committed collectives.json is
    exactly the current partitioned program's inventory, every reshard/
    replication hazard is fixed or carries a written rationale, and the
    1M-resource per-shard projection clears the capacity SLO."""
    program = build_program()
    assert program.worker_error is None, program.worker_error

    golden = json.loads(open(COLLECTIVES_PATH).read())
    assert set(golden["entries"]) == {e.name for e in program.entries}
    for e in program.entries:
        g = golden["entries"][e.name]
        cur = group_collectives(e.collectives)
        assert cur == g["collectives"], f"{e.name}: ledger drifted — review, then --update-collectives"
        assert ledger_bytes(cur) == g["bytes_per_tick"]

    case = program.budget_case()
    assert case is not None and case.shard_bytes > 0
    assert case.shard_bytes < capacity_slo_bytes()

    findings = run_spmd_analysis(program=program)
    assert findings == [], "\n".join(
        f"{f.path}:{f.line} [{f.rule}] {f.message}" for f in findings
    )


def test_known_salsa_read_reshard_is_pinned_and_rationalized():
    """The hazard this tier found: the salsa running-sum read flattens
    the width-sharded table, so XLA all-gathers the full [depth, width]
    slice each tick.  It must stay pinned in the golden (2 gathers) and
    carry a written rationale at the flatten site — if either goes, the
    analyzer's empty-findings gate above is lying."""
    golden = json.loads(open(COLLECTIVES_PATH).read())
    tick = golden["entries"]["tick/sketch-salsa"]
    gathers = [
        g for g in tick["collectives"]
        if g["kind"] == "all-gather" and g["shape"] == [2, 512]
    ]
    assert gathers and gathers[0]["count"] == 2
    src = open(os.path.join(REPO_ROOT, "sentinel_tpu/ops/tables.py")).read()
    assert "stlint: disable-next-line=implicit-reshard" in src


def test_tier4_baseline_is_empty():
    """Tier 4 launched with ZERO accepted debt — hazards get fixed or a
    written rationale, never a baseline bump."""
    from sentinel_tpu.analysis import DEFAULT_BASELINE, load_baseline

    spmd_rules = {p.name for p in ALL_SPMD_PASSES}
    offenders = [
        k for k in load_baseline(DEFAULT_BASELINE) if k.split(":")[0] in spmd_rules
    ]
    assert offenders == []


def test_spmd_gauges_exported_on_registry():
    """The analyzer's measurements ride the obs registry so the
    profiling plane and the README catalog can see them."""
    from sentinel_tpu.obs.registry import REGISTRY

    build_program()  # cached worker; idempotent re-export
    series = REGISTRY.series("sentinel_spmd_collective_bytes_per_tick")
    entries = {dict(m.labels)["entry"]: m.value for m in series}
    assert "tick/sketch-salsa" in entries
    assert entries["tick/sketch-salsa"] > 0
    hbm = REGISTRY.get("sentinel_spmd_shard_hbm_projected_bytes")
    assert hbm is not None and 0 < hbm.value < capacity_slo_bytes()


# ---------------------------------------------------------------------------
# topology hygiene: tier-4 never touches the parent's devices
# ---------------------------------------------------------------------------


def test_parent_device_topology_unchanged_by_tier4_run():
    """The worker forces an 8-device CPU platform in a SUBPROCESS; the
    tier-1 suite's own jax topology must be identical before and after a
    full tier-4 run (backend re-init inside a live process would poison
    every cached executable)."""
    import jax

    before = [str(d) for d in jax.devices()]
    backend_before = jax.default_backend()
    findings = run_spmd_analysis()  # full tier, worker cached or fresh
    assert [str(d) for d in jax.devices()] == before
    assert jax.default_backend() == backend_before
    assert isinstance(findings, list)
