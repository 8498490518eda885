"""The readers of the program's phase spans and counters, on hand-built
spans: a known value from a known span list, and nothing to read when the
run was untraced, when spans were dropped, or when the program records no
such span (the parent commit of these readers' spans)."""

import dataclasses

import pytest

from benchmarks.chip import devtrace, harness

MS = 1_000_000


@dataclasses.dataclass
class Span:
    name: str
    trace: str
    sid: int
    parent: int | None
    wall_ns_start: float
    wall_ns_end: float
    cpu_ns: int | None = None


def served_batch(trace: str, t: float, wait_ms: float = 3.0,
                 reroute: bool = False) -> list[Span]:
    """One batch's spans from ``t`` ns on, as the lane records a batch whose
    encode and pack run on the device, each phase on CPU half its wall
    time: idle 10 ms, form 2, pad 1, encode (the buffer's put) 4, dispatch
    2, device wait ``wait_ms``, readback 2, the dense reroute 6 (its own
    forward and dispatch 5 inside it), complete 1."""
    spans: list[Span] = []

    def add(name, parent, ms, at=None):
        start = t if at is None else at
        s = Span(name, trace, len(spans), parent, start, start + ms * MS,
                 int(ms * MS / 2))
        spans.append(s)
        return s

    t = add("lane.idle", None, 10).wall_ns_end
    t = add("batch.form", None, 2).wall_ns_end
    batch = add("batch", None, 0)
    pad = add("lane.pad", batch.sid, 1)
    runtime = add("runtime", batch.sid, 0, at=pad.wall_ns_end)
    t = pad.wall_ns_end
    t = add("lane.encode", runtime.sid, 4).wall_ns_end
    fwd = add("accel.forward", runtime.sid, 2)
    t = add("accel.dispatch", fwd.sid, 2).wall_ns_end
    for name, ms in (("lane.device_wait", wait_ms), ("lane.readback", 2)):
        t = add(name, runtime.sid, ms).wall_ns_end
    if reroute:
        rr = add("lane.reroute", runtime.sid, 6)
        dense = add("accel.forward", rr.sid, 5)
        add("accel.dispatch", dense.sid, 5)
        t = rr.wall_ns_end
    runtime.wall_ns_end = batch.wall_ns_end = t
    add("batch.complete", None, 1)
    for s in (batch, runtime, fwd):
        s.cpu_ns = None                      # begin/end spans carry none
    return spans


def make_run(spans, t0=1.0, seconds=1.0, dropped=0, trace=None, stats=None):
    return harness.Run(cell=None, seconds=seconds, t0=t0, setup_s=0.0,
                       records=None, correct_rows=None, events=None,
                       widths=None, stats=stats or {"batches": 0}, peak={},
                       spans=spans, spans_dropped=dropped, trace=trace,
                       offset_ns=0.0)


# two batches in the window [1 s, 2 s], the second with a 60-ms device wait
# and the dense reroute; a third opens after the window and is left out
SPANS = (served_batch("batch-000000", 1.1e9)
         + served_batch("batch-000001", 1.5e9, wait_ms=60.0, reroute=True)
         + served_batch("batch-000002", 2.5e9, wait_ms=1000.0))

KNOWN = {
    "pad_ms_per_batch": 1.0,
    "encode_ms_per_batch": 4.0,
    "dispatch_ms_per_batch": 2.0,            # the dense one is in the reroute
    "device_wait_ms_per_batch": (3.0 + 60.0) / 2,
    "readback_ms_per_batch": (2.0 + 2.0 + 6.0) / 2,
    "complete_ms_per_batch": 1.0,
    "form_wait_ms": 2.0,
    "lane_cpu_pct": 50.0,
    "host_stall_s": 0.060,
}
SPAN_READERS = sorted(KNOWN) + ["idle_unattributed_pct"]


@pytest.mark.parametrize("name", sorted(KNOWN))
def test_reader_gives_the_known_value(name):
    assert harness.reader(name)(make_run(SPANS)) == pytest.approx(KNOWN[name])


def test_phases_add_up_to_the_batch():
    """The five phases a batch's host and device-call time is made of sum to
    the batch span's pad-to-runtime-end extent in this synthetic run."""
    parts = ("pad", "encode", "dispatch", "device_wait", "readback")
    total = sum(harness.reader(f"{p}_ms_per_batch")(make_run(SPANS))
                for p in parts)
    batches = [s for s in SPANS if s.name == "batch"][:2]
    pad = [s for s in SPANS if s.name == "lane.pad"][:2]
    per_batch = sum(b.wall_ns_end - p.wall_ns_start
                    for b, p in zip(batches, pad)) / 2 / MS
    assert total == pytest.approx(per_batch)


def test_idle_unattributed_share():
    # window [0, 100] ns; the device busy 60-70; lane spans cover 0-60 and
    # 70-80, a runtime span (not the lane's) all of it: 20 of 90 idle ns
    spans = [Span("lane.idle", "b", 0, None, 0, 20),
             Span("batch.form", "b", 1, None, 20, 30),
             Span("lane.pad", "b", 2, None, 30, 35),
             Span("lane.encode", "b", 3, None, 35, 60),
             Span("lane.readback", "b", 4, None, 70, 80),
             Span("runtime", "b", 5, None, 0, 100)]
    tr = devtrace.Trace([("kernel", 60, 70)], 1, (0, 100))
    run = make_run(spans, t0=0.0, seconds=100e-9, trace=tr)
    value = harness.reader("idle_unattributed_pct.sat")(run)
    assert value == pytest.approx(100.0 * 20 / 90)


@pytest.mark.parametrize("name", SPAN_READERS)
def test_nothing_to_read_untraced_dropped_or_without_the_span(name):
    read = harness.reader(name)
    tr = devtrace.Trace([("kernel", 1.05e9, 1.06e9)], 1, (1e9, 2e9))
    assert read(make_run(None)) is None                      # untraced
    assert read(make_run(SPANS, dropped=3, trace=tr)) is None
    # the spans a program without phase spans records
    old = [Span("batch", "b", 0, None, 1.1e9, 1.2e9),
           Span("lane", "b", 1, 0, 1.1e9, 1.2e9),
           Span("runtime", "b", 2, 1, 1.1e9, 1.2e9),
           Span("accel.forward", "b", 3, 2, 1.15e9, 1.16e9),
           Span("accel.kernel", "b", 4, 3, 1.15e9, 1.16e9)]
    assert read(make_run(old, trace=tr)) is None


def test_lane_cpu_needs_cpu_time():
    spans = [dataclasses.replace(s, cpu_ns=None) for s in SPANS]
    assert harness.reader("lane_cpu_pct.sat")(make_run(spans)) is None


def test_exit_step_mean_reads_the_counter():
    read = harness.reader("exit_step_mean.rate")
    assert read(make_run(None, stats={"batches": 5, "mean_steps": 7.5})) \
        == 7.5
    assert read(make_run(None, stats={"batches": 5})) is None
    assert read(make_run(None, stats={"batches": 0, "mean_steps": 0.0})) \
        is None
