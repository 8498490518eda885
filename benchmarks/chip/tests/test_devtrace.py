"""The trace reduction on hand-built intervals and on a small recorded trace
of the served path on a TPU v5e."""

import dataclasses
import json
from pathlib import Path

import pytest

from benchmarks.chip import devtrace

RECORDED = Path(__file__).resolve().parent / "data" / "trace_excerpt.json"


def make(ops, anchor=(0.0, 100.0), n=1):
    return devtrace.Trace([tuple(o) for o in ops], n, anchor)


def test_busy_is_the_union_of_overlapping_ops():
    tr = make([("a", 10, 20), ("b", 15, 30), ("c", 50, 60), ("d", 55, 58)])
    assert devtrace.merge((s, e) for _, s, e in tr.ops) == [(10, 30), (50, 60)]
    assert devtrace.busy_share(tr, 0, 100) == pytest.approx(30e-9)
    # clipped to the window
    assert devtrace.busy_share(tr, 20, 55) == pytest.approx(15e-9)


def test_busy_is_averaged_over_devices():
    tr = make([("a", 0, 40), ("b", 0, 20)], n=2)
    assert devtrace.busy_share(tr, 0, 100) == pytest.approx(20e-9)


def test_gaps_cover_the_rest_of_the_window():
    busy = [(10, 30), (50, 60)]
    assert devtrace.gaps(busy, 0, 100) == [(0, 10), (30, 50), (60, 100)]
    assert devtrace.gaps(busy, 15, 55) == [(30, 50)]


def test_top_ops_sum_by_instruction_name():
    tr = make([("%k.1 = s32[2] custom-call(a)", 0, 10),
               ("%k.1 = s32[2] custom-call(b)", 20, 25), ("%m", 30, 32)])
    assert devtrace.top_ops(tr, 0, 100) == [["%k.1", 15e-9], ["%m", 2e-9]]


@dataclasses.dataclass
class Span:
    name: str
    trace: str
    sid: int
    parent: int | None
    wall_ns_start: float
    wall_ns_end: float


def test_idle_is_split_by_what_the_host_was_doing():
    spans = [Span("batch", "b0", 0, None, 5, 95),
             Span("runtime", "b0", 2, 1, 20, 80),
             Span("accel.forward", "b0", 3, 2, 40, 45)]
    tr = make([("encode", 25, 30), ("kernel", 45, 70)])
    out = devtrace.idle_by_host_state(tr, spans, 0.0, 0, 100, longest=2)
    totals = {k: v for k, v in out if k.startswith("total:")}
    assert totals == pytest.approx({
        "total:no_batch_open": 10e-9,        # 0-5, 95-100
        "total:batch_bookkeeping": 30e-9,    # 5-20, 80-95
        "total:encode_pack": 15e-9,          # 20-25, 30-40
        "total:dispatch": 5e-9,              # 40-45
        "total:readback": 10e-9})            # 70-80
    assert sum(totals.values()) == pytest.approx(70e-9)
    # the two longest single gaps, each named by its largest state
    longest = [(k, v) for k, v in out if k.startswith("gap:")]
    assert longest == [("gap:batch_bookkeeping@0.000", pytest.approx(30e-9)),
                       ("gap:batch_bookkeeping@0.000", pytest.approx(25e-9))]


def test_kernel_rule_finds_the_fused_kernel_in_a_recorded_trace():
    rec = json.loads(RECORDED.read_text())
    tr = make(rec["ops"], tuple(rec["anchor"]))
    evs = devtrace.kernel_events(tr, "fused_event_lif")
    assert evs, "the kernel rule matches no op of the recorded trace"
    assert len(evs) == rec["fused_event_lif_calls"]
    names = {n for n, _, _ in tr.ops} - {n for n, _, _ in evs}
    assert names, "the rule must not match every op"
    lo, hi = min(s for _, s, _ in tr.ops), max(e for _, _, e in tr.ops)
    busy = devtrace.busy_share(tr, lo, hi)
    assert 0 < busy <= (hi - lo) / 1e9
    assert sum(e - s for _, s, e in evs) / 1e9 <= busy
