"""PR 36's per-layer metrics, held to a hand-made span list (the rehearsal
runs untraced): what a thread waited for, the hot-set pass by its parts, the
read-back's copy."""

import numpy as np
import pytest

from perfbench import manifest as M
from perfbench.generators import Window
from perfbench.readers import Context, span_share

MS = 1_000_000
MANIFEST = M.load()

LATENCY = ["zipf-1m.paced", "zipf-10k.paced", "zipf-10k.entry", "param-1m-hot-keys.paced",
           "rls-mesh-4096.paced"]
FLOOD = ["zipf-1m.flood"]
#: not the entry cell, whose 4 s traced window may hold no pass at all (PERF.md, PR 36)
HOTSET = LATENCY[:2]
PROCESS = "process (interpreter lock, collector, host scheduler)"

#: metric -> (reader, cells, layer's first words, moves, what SPANS reads as)
EXPECTED = {
    "wake_late_ms": ("span_each", LATENCY, "process", "decision_p50_ms", (0.1 + 0.3 + 26.0) / 3),
    "wake_late_ms.flood": ("span_each", FLOOD, "process", "decisions_per_s", (0.1 + 0.3 + 26.0) / 3),
    "stall_max_ms": ("span_each", LATENCY, "process", "decision_p95_ms", 26.0),
    "stall_max_ms.flood": ("span_each", FLOOD, "process", "decisions_per_s", 26.0),
    "gc_share_pct": ("span_share", LATENCY, "process", "decision_p95_ms", 100.0 * (12 + 8) / 2000),
    "gc_share_pct.flood": ("span_share", FLOOD, "process", "decisions_per_s", 100.0 * (12 + 8) / 2000),
    "hotset_pass_ms": ("span_each", HOTSET, "host tick pipeline", "decision_p95_ms", 38.0),
    "hotset_demote_ms": ("span_each", HOTSET, "host tick pipeline", "decision_p95_ms", 1.5),
    "hotset_health_ms": ("span_each", HOTSET, "host tick pipeline", "decision_p95_ms", 33.0),
    "readback_copy_ms": ("span_attr", LATENCY[:4], "request surface", "decision_p50_ms", 0.5),
}


def span(name, trace, t0_ms, dur_ms, **attrs):
    return {"name": name, "trace": trace, "t0_ns": int(t0_ms * MS),
            "dur_ns": int(dur_ms * MS), "attrs": attrs}


#: a 2 s window: three beats (one held 26 ms), two collections (the second
#: runs 4 ms past the window's close), two hot-set passes, two ticks
SPANS = [
    span("proc.wake", 0, 5, 0.1), span("proc.wake", 0, 10, 0.3),
    span("proc.wake", 0, 15, 26.0, cpu_ns=25 * MS),
    span("proc.gc", 0, 100, 12, gen=2, collected=7), span("proc.gc", 0, 1992, 12, gen=1, collected=0),
    span("tick.hotset", 0, 200, 36, **{"pass": 1}), span("tick.hotset", 0, 1200, 40, **{"pass": 2}),
    span("hotset.scan", 1, 200, 1), span("hotset.demote", 1, 201, 2, rows=3, stats_reads=3, demoted=0),
    span("hotset.health", 1, 203, 32, lock_ns=4 * MS, read_ns=27 * MS),
    span("hotset.scan", 2, 1200, 1), span("hotset.demote", 2, 1201, 1, rows=3, stats_reads=3, demoted=1),
    span("hotset.recompile", 2, 1202, 4), span("hotset.health", 2, 1206, 34, lock_ns=0, read_ns=33 * MS),
    span("tick.wait", 7, 300, 4, copy_ns=int(0.4 * MS)), span("tick.wait", 8, 310, 3, copy_ns=int(0.6 * MS)),
    span("tick.wait", 9, 320, 3),  # a program from before copy_ns: skipped
]


def ctx(spans=SPANS):
    win = Window(seconds=2.0, open_ns=0, close_ns=2 * 10**9, attempted=4, failed=0,
                 latency_ms=np.array([10.0, 20.0, 30.0, 40.0]), due_ns=np.zeros(4),
                 visible_items=1000, late_ms=np.array([0.5, 1.5]), passes=np.zeros(1),
                 codes={}, unresolved=0, span_s=2.0)
    return Context(window=win, setup_s=12.5, batch=256, spans=spans)


def test_the_grown_manifest_is_sound_and_only_grew():
    assert M.problems(MANIFEST) == []
    names = [m["name"] for m in MANIFEST["per_layer"]]
    assert names[-len(EXPECTED):] == list(EXPECTED)  # appended, in the issue's order
    assert {m["layer"] for m in MANIFEST["per_layer"] if m["name"] in EXPECTED and
            m["layer"].startswith("process")} == {PROCESS}


@pytest.mark.parametrize("name", list(EXPECTED))
def test_a_new_metric_resolves_to_its_file_and_reader(name):
    reader, cells, layer, moves, _ = EXPECTED[name]
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
    assert entry["workloads"] == cells and entry["moves"] == moves
    assert entry["layer"].startswith(layer) and entry["source"] == "program_span"
    assert entry["better"] == "lower" and entry["unit"] == ("%" if "pct" in name else "ms")
    spec = M.metric(name)
    assert spec["reader"] == reader and callable(M.module("readers", reader).read)
    for cell in cells:
        assert entry in M.metrics_of(MANIFEST, cell, "per_layer")


@pytest.mark.parametrize("name", list(EXPECTED))
def test_a_new_metric_reads_the_recorded_spans(name):
    spec = M.metric(name)
    got = M.module("readers", spec["reader"]).read(ctx(), **spec["args"])
    assert got == pytest.approx(EXPECTED[name][4])


@pytest.mark.parametrize("name", [n for n in EXPECTED if not n.startswith("gc_share")])
def test_a_program_without_the_span_reads_as_nothing_and_does_not_raise(name):
    """The parent's side of this PR's check: its spans, none of the new ones."""
    older = [dict(s, attrs={k: v for k, v in s["attrs"].items() if k not in ("copy_ns", "pass")})
             for s in SPANS if not s["name"].startswith(("proc.", "hotset."))]
    spec = M.metric(name)
    got = M.module("readers", spec["reader"]).read(ctx(older), **spec["args"])
    assert got is None or name == "hotset_pass_ms"  # tick.hotset was there before


@pytest.mark.parametrize("spans, want", [
    (SPANS, 1.0),  # 12 ms and the 8 ms of the second that lie inside the window
    ([s for s in SPANS if s["name"] != "proc.gc"], 0.0),  # a window without a collection
    ([], None),  # nothing recorded at all: an untraced run
])
def test_span_share_is_zero_for_a_quiet_window_and_nothing_for_an_untraced_one(spans, want):
    got = span_share.read(ctx(spans), "proc.gc")
    assert got == want and type(got) is type(want)
