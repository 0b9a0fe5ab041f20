"""SPMD-tier (tier-4) analysis framework.

Tier 2 sees the traced PROGRAM; this tier sees the PARTITIONED program —
each real entry point lowered under the blessed 8-device CPU mesh
(``parallel/meshspec.py``) with the shardings ``parallel/spmd.py``
declares, compiled through GSPMD, and read back as optimized HLO.  The
objects of study are what partitioning ADDS: the collectives XLA placed
(all-gather / all-reduce / reduce-scatter / collective-permute /
all-to-all, each with its per-tick bytes over the interconnect), the
implicit reshards it resolved silently, and the per-shard byte footprint
the declared specs imply.

Findings reuse the tier-1 :class:`Finding`/baseline machinery.  Where a
collective carries HLO source metadata the finding lands on the real
``file:line`` (so ``# stlint: disable=`` comments apply); program-level
findings anchor on the entry's pseudo-path ``spmd://<entry-name>`` and
config-level ones on ``spmd://config/<config-name>``.

Everything in this module is mesh-free and jax-free-at-import: the
passes run in the PARENT process over a plain-data report produced by
the forced-topology subprocess (worker.py via runner.py), which keeps
them unit-testable on synthetic fixtures and keeps the parent's jax
device topology untouched.
"""

from __future__ import annotations

import os
import re
from dataclasses import dataclass, field
from typing import Any, Dict, Iterable, List, Optional, Tuple

from sentinel_tpu.analysis.framework import ERROR, Finding

#: directory of the golden file (collectives.json)
SPMD_DIR = os.path.dirname(os.path.abspath(__file__))
COLLECTIVES_PATH = os.path.join(SPMD_DIR, "collectives.json")

#: HLO primitive byte widths (shapes printed by the partitioner are
#: per-device buffer shapes)
DTYPE_BYTES = {
    "pred": 1,
    "s8": 1, "u8": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}


def hlo_dtype(name: str) -> str:
    """HLO spelling of a numpy dtype name ("int32" -> "s32")."""
    import numpy as np

    if name == "bfloat16":
        return "bf16"
    dt = np.dtype(name)
    if dt.kind == "b":
        return "pred"
    return {"i": "s", "u": "u", "f": "f"}[dt.kind] + str(dt.itemsize * 8)


#: collective ops the ledger tracks (async "-start" forms fold into the
#: base kind; "-done" carries no new transfer)
COLLECTIVE_KINDS = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "collective-permute",
    "all-to-all",
)


@dataclass(frozen=True)
class Collective:
    """One collective instruction in the partitioned HLO."""

    kind: str  # e.g. "all-gather"
    dtype: str  # HLO dtype, e.g. "s32"
    shape: Tuple[int, ...]  # per-device RESULT buffer shape
    source: Optional[str] = None  # repo-relative path from HLO metadata
    line: int = 0

    @property
    def nbytes(self) -> int:
        n = DTYPE_BYTES.get(self.dtype, 4)
        for d in self.shape:
            n *= d
        return n


@dataclass(frozen=True)
class ConstInfo:
    """One jaxpr const closed over by an entry (replicated by construction)."""

    dtype: str
    shape: Tuple[int, ...]
    nbytes: int


@dataclass(frozen=True)
class LeafPlacement:
    """One state leaf folded with its declared PartitionSpec."""

    name: str  # pytree key path, e.g. ".win_sec.counts"
    dtype: str
    shape: Tuple[int, ...]
    spec: Tuple[Optional[str], ...]  # mesh axis (or None) per dimension
    global_bytes: int
    shard_bytes: int  # projected per-device bytes under the spec

    @property
    def sharded(self) -> bool:
        return any(a is not None for a in self.spec)


@dataclass
class ShardedEntry:
    """One lowered+partitioned entry point: the unit the HLO passes run over."""

    name: str  # e.g. "tick/sketch-salsa"
    collectives: List[Collective] = field(default_factory=list)
    consts: List[ConstInfo] = field(default_factory=list)
    placements: List[LeafPlacement] = field(default_factory=list)

    @property
    def pseudo_path(self) -> str:
        return f"spmd://{self.name}"


@dataclass
class ConfigCase:
    """One blessed config's state leaves folded with the declared specs —
    enough for divisibility and byte math WITHOUT lowering anything."""

    name: str  # e.g. "bench/sketch-1m"
    placements: List[LeafPlacement] = field(default_factory=list)

    @property
    def pseudo_path(self) -> str:
        return f"spmd://config/{self.name}"

    @property
    def shard_bytes(self) -> int:
        return sum(p.shard_bytes for p in self.placements)


@dataclass
class SpmdProgram:
    """Everything the tier-4 passes consume, as plain data."""

    n_devices: int
    axis: str
    entries: List[ShardedEntry] = field(default_factory=list)
    configs: List[ConfigCase] = field(default_factory=list)
    #: name of the ConfigCase the HBM budgeter projects (the 1M-resource
    #: sketch tier); None disables the budget pass
    budget_config: Optional[str] = None
    capacity_bytes: int = 0
    golden: Optional[Dict[str, Any]] = None
    jax_version: str = ""
    #: non-None when the forced-topology subprocess failed — the ledger
    #: pass surfaces it loudly instead of reporting a silently-empty tier
    worker_error: Optional[str] = None

    def budget_case(self) -> Optional[ConfigCase]:
        for c in self.configs:
            if c.name == self.budget_config:
                return c
        return None


class SpmdPass:
    """One pass over the partitioned program."""

    name: str = ""
    description: str = ""
    severity: str = ERROR

    def run(self, program: SpmdProgram) -> Iterable[Finding]:  # pragma: no cover
        raise NotImplementedError

    def finding(
        self,
        path: str,
        message: str,
        severity: Optional[str] = None,
        line: int = 1,
    ) -> Finding:
        return Finding(
            rule=self.name,
            path=path,
            line=line,
            col=0,
            message=message,
            severity=severity or self.severity,
        )


# -- HLO parsing -------------------------------------------------------------

# `  %all-gather.12 = s32[2,512]{1,0} all-gather(...), ..., metadata={...
# stack_frame_id=72}` — the frame id indexes the module's stack-frame
# tables, printed once in the HLO text header:
#   FileNames        `3 "/abs/sentinel_tpu/ops/tables.py"`
#   FileLocations    `9 {file_name_id=3 function_name_id=5 line=255 ...}`
#   StackFrames      `72 {file_location_id=9 parent_frame_id=70}`
# A frame's own location is the innermost user line of the op.
_INSTR_RE = re.compile(
    r"=\s+(?P<dtype>\w+)\[(?P<shape>[\d,]*)\]\S*\s+"
    r"(?P<kind>" + "|".join(COLLECTIVE_KINDS) + r")(?:-start)?\("
)
# `%all-reduce.20 = (f32[1]{0}, f32[63]{0}) all-reduce(...)`: XLA's
# combiner merges same-kind collectives into ONE tuple-shaped op — each
# element is still its own transfer
_TUPLE_INSTR_RE = re.compile(
    r"=\s+\((?P<elems>[^()]*)\)\s+"
    r"(?P<kind>" + "|".join(COLLECTIVE_KINDS) + r")\("
)
_ELEM_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_FRAME_RE = re.compile(r"stack_frame_id=(\d+)")
_TABLE_ROW_RE = re.compile(r"\s*(\d+)\s+(.*)")
_LOC_RE = re.compile(r"file_name_id=(\d+)\b.*?\bline=(\d+)")
_FRAME_LOC_RE = re.compile(r"file_location_id=(\d+)")


def _frame_sources(lines: List[str]) -> Dict[int, Tuple[str, int]]:
    """stack_frame_id -> (absolute file, line) from the header tables."""
    files: Dict[int, str] = {}
    locs: Dict[int, Tuple[int, int]] = {}
    frames: Dict[int, int] = {}
    section = None
    for ln in lines:
        head = ln.strip()
        if head in ("FileNames", "FunctionNames", "FileLocations", "StackFrames"):
            section = head
            continue
        if section is None:
            continue
        row = _TABLE_ROW_RE.fullmatch(ln)
        if row is None:
            section = None  # blank line / first computation ends a table
            continue
        idx, rest = int(row.group(1)), row.group(2)
        if section == "FileNames":
            files[idx] = rest.strip('"')
        elif section == "FileLocations":
            m = _LOC_RE.search(rest)
            if m:
                locs[idx] = (int(m.group(1)), int(m.group(2)))
        elif section == "StackFrames":
            m = _FRAME_LOC_RE.search(rest)
            if m:
                frames[idx] = int(m.group(1))
    out: Dict[int, Tuple[str, int]] = {}
    for fid, loc in frames.items():
        if loc in locs and locs[loc][0] in files:
            out[fid] = (files[locs[loc][0]], locs[loc][1])
    return out


def parse_hlo_collectives(
    hlo_text: str, repo_root: Optional[str] = None
) -> List[Collective]:
    """Every collective instruction in an optimized-HLO dump.

    Shapes are the per-device result buffers the partitioner printed.
    A combined (tuple-shaped) collective counts once per element; the
    "-done" side of an async pair is skipped so each transfer counts once.
    """
    lines = hlo_text.splitlines()
    sources = _frame_sources(lines)
    out: List[Collective] = []
    for ln in lines:
        m = _INSTR_RE.search(ln)
        if m:
            kind = m.group("kind")
            elems = [(m.group("dtype"), m.group("shape"))]
        else:
            m = _TUPLE_INSTR_RE.search(ln)
            if not m:
                continue
            kind = m.group("kind")
            elems = _ELEM_RE.findall(m.group("elems"))
        src: Optional[str] = None
        line = 0
        fm = _FRAME_RE.search(ln)
        if fm and int(fm.group(1)) in sources:
            fn, line = sources[int(fm.group(1))]
            if repo_root:
                try:
                    rel = os.path.relpath(fn, repo_root).replace(os.sep, "/")
                except ValueError:
                    rel = fn
                src = None if rel.startswith("..") else rel
            else:
                src = fn
        for dtype, dims in elems:
            out.append(
                Collective(
                    kind=kind,
                    dtype=dtype,
                    shape=tuple(int(d) for d in dims.split(",") if d),
                    source=src,
                    line=line,
                )
            )
    return out


def group_collectives(colls: Iterable[Collective]) -> List[Dict[str, Any]]:
    """Collectives grouped by (kind, dtype, shape) — the golden's unit.

    Source lines are deliberately NOT part of the key: they drift with
    every unrelated edit, while the (kind, shape, count) inventory only
    moves when the partitioned program really changes.
    """
    acc: Dict[Tuple[str, str, Tuple[int, ...]], Dict[str, Any]] = {}
    for c in colls:
        key = (c.kind, c.dtype, c.shape)
        g = acc.get(key)
        if g is None:
            acc[key] = {
                "kind": c.kind,
                "dtype": c.dtype,
                "shape": list(c.shape),
                "count": 1,
                "bytes_each": c.nbytes,
            }
        else:
            g["count"] += 1
    return sorted(
        acc.values(),
        key=lambda g: (g["kind"], g["dtype"], tuple(g["shape"])),
    )


def ledger_bytes(groups: Iterable[Dict[str, Any]]) -> int:
    """Per-tick bytes over the interconnect for a grouped inventory."""
    return sum(int(g["count"]) * int(g["bytes_each"]) for g in groups)
