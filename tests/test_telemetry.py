"""Telemetry subsystem: deterministic span trees (scope tags, logical
clocks, bit-identical canonical form across seeded runs), the metrics
registry (consistent snapshots, fixed-bucket histograms, typed events), and
the JSONL / Prometheus exporters."""

import json
import threading
import time

import numpy as np
import pytest

from repro.telemetry import export as texport
from repro.telemetry import trace as ttrace
from repro.telemetry.metrics import (DEPTH_BUCKETS, Histogram,
                                     MetricsRegistry)
from repro.telemetry.trace import SCOPES, NullRecorder, Tracer

from _fakes import tiny_emax_artifact


# ------------------------------------------------------------------- tracing
def test_scope_tag_is_mandatory_and_closed():
    t = Tracer()
    with pytest.raises(ValueError, match="accel"):
        t.begin("x", "device")
    with pytest.raises(ValueError, match="scope"):
        t.emit("x", "host")
    assert SCOPES == ("accel", "system")


def test_context_manager_nesting_builds_the_tree():
    t = Tracer()
    with t.span("a", "system") as a:
        with t.span("b", "accel"):
            t.emit("c", "accel")
        t.emit("d", "system")
    spans = {s.name: s for s in t.sorted_spans()}
    assert spans["a"].parent is None
    assert spans["b"].parent == spans["a"].sid
    assert spans["c"].parent == spans["b"].sid          # nests under inner
    assert spans["d"].parent == spans["a"].sid          # inner already popped
    assert len({s.trace for s in spans.values()}) == 1  # one auto trace
    assert [spans[n].sid for n in "abcd"] == [0, 1, 2, 3]


def test_begin_end_crosses_threads_and_merges_attrs():
    t = Tracer()
    sp = t.begin("request", "system", trace="req-00000001",
                 attrs={"rid": 1})

    def closer():
        t.end(sp, attrs={"label": 7})

    th = threading.Thread(target=closer)
    th.start()
    th.join()
    assert sp.attrs == {"rid": 1, "label": 7}
    assert sp.wall_ns_end >= sp.wall_ns_start
    # begin() does not touch the nesting stack
    assert t.current() is None


def test_sids_are_sequential_per_trace():
    t = Tracer()
    t.begin("a", "system", trace="x")
    t.begin("b", "system", trace="y")
    t.begin("c", "system", trace="x")
    sids = {(s.trace, s.name): s.sid for s in t.sorted_spans()}
    assert sids[("x", "a")] == 0 and sids[("x", "c")] == 1
    assert sids[("y", "b")] == 0


def test_emit_is_zero_wall_duration():
    t = Tracer()
    s = t.emit("board.image", "accel", attrs={"cycles": 42})
    assert s.wall_ns_start == s.wall_ns_end
    assert s.attrs == {"cycles": 42}


def test_canonical_excludes_wall_and_meta():
    t = Tracer()
    with t.span("a", "system", attrs={"k": 1}, meta={"lane": 3}):
        pass
    (c,) = t.canonical()
    assert c == {"trace": c["trace"], "sid": 0, "parent": None, "name": "a",
                 "scope": "system", "attrs": {"k": 1}}
    (f,) = [s.full() for s in t.sorted_spans()]
    assert f["meta"] == {"lane": 3}
    assert "wall_ns_start" in f and "wall_ns_end" in f


def test_fingerprint_bit_identical_across_runs():
    def run():
        t = Tracer()
        with t.span("forward", "system", trace="t0", attrs={"batch": 4}):
            for i in range(4):
                t.emit("image", "accel", attrs={"i": i, "cycles": 10 * i})
        return t

    t1, t2 = run(), run()
    assert t1.fingerprint() == t2.fingerprint()
    assert t1.canonical() == t2.canonical()
    t3 = run()
    t3.emit("extra", "system", trace="t0")
    assert t3.fingerprint() != t1.fingerprint()


def test_max_spans_bound_drops_and_counts():
    t = Tracer(max_spans=3)
    got = [t.emit("e", "system", trace="t0") for _ in range(5)]
    assert len(t.spans) == 3 and t.dropped == 2
    assert got[3] is None and got[4] is None
    t.end(got[4])                                       # end(None) is safe


def test_roots_children_find():
    t = Tracer()
    r = t.begin("batch", "system", trace="b0")
    t.emit("lane", "system", trace="b0", parent=r.sid)
    t.emit("lane", "system", trace="b1")
    assert [s.trace for s in t.roots("batch")] == ["b0"]
    assert [s.name for s in t.children(r)] == ["lane"]
    assert len(t.find("lane")) == 2
    assert len(t.find("lane", trace="b0")) == 1


def test_module_recorder_disabled_by_default():
    rec = ttrace.get()
    assert isinstance(rec, NullRecorder) and not rec.enabled
    assert not ttrace.enabled()
    # zero-allocation singletons on the disabled path
    assert rec.span("a", "system") is rec.span("b", "accel")
    assert rec.begin("a", "system") is None
    assert rec.emit("a", "system") is None
    rec.end(None, attrs={"x": 1})                       # no-op, no raise
    with ttrace.span("a", "system") as s:
        assert s is None


def test_install_swaps_and_restores():
    t = Tracer()
    prev = ttrace.install(t)
    try:
        assert ttrace.get() is t and ttrace.enabled()
        ttrace.emit("e", "system", trace="t0")
        assert len(t.spans) == 1
    finally:
        assert ttrace.install(prev) is t
    assert not ttrace.enabled()


# ------------------------------------------------------------------- metrics
def test_counter_gauge_peak():
    m = MetricsRegistry()
    m.inc("images_out", 4)
    m.inc("images_out")
    m.set_gauge("depth", 3.0)
    m.set_max("peak", 5.0)
    m.set_max("peak", 2.0)                              # lower: ignored
    snap = m.snapshot()
    assert snap["images_out"] == 5
    assert snap["depth"] == 3.0 and snap["peak"] == 5.0


def test_histogram_fixed_buckets_and_exact_percentiles():
    rng = np.random.RandomState(0)
    vals = rng.exponential(100.0, size=500)
    h = Histogram("lat", (50.0, 100.0, 250.0))
    for v in vals:
        h.observe(v)
    assert h.count == 500 and h.sum == pytest.approx(vals.sum())
    assert sum(h.counts) == 500
    assert h.counts[0] == int((vals <= 50.0).sum())
    assert h.counts[-1] == int((vals > 250.0).sum())    # +inf bucket
    for q in (50, 95, 99):
        assert h.percentile(q) == pytest.approx(np.percentile(vals, q))
    assert h.mean() == pytest.approx(vals.mean())
    assert Histogram("e", (1.0,)).percentile(50) == 0.0  # empty -> 0


def test_histogram_boundaries_are_pinned():
    m = MetricsRegistry()
    m.histogram("lat", DEPTH_BUCKETS)
    m.histogram("lat", DEPTH_BUCKETS)                   # idempotent
    with pytest.raises(ValueError, match="already registered"):
        m.histogram("lat", (1.0, 2.0))
    with pytest.raises(ValueError, match="sorted"):
        Histogram("bad", (2.0, 1.0))


def test_histogram_window_is_bounded_but_totals_exact():
    m = MetricsRegistry()
    m.histogram("lat", DEPTH_BUCKETS, window=8)
    for v in range(100):
        m.observe("lat", float(v), DEPTH_BUCKETS)
    snap = m.snapshot()
    assert snap["lat_count"] == 100                     # totals: exact
    assert snap["lat_sum"] == pytest.approx(sum(range(100)))
    assert snap["lat_p50"] == pytest.approx(95.5)       # window: last 8


def test_typed_events_and_bounded_ring():
    class Tiny(MetricsRegistry):
        EVENT_WINDOW = 4

    m = Tiny()
    for i in range(6):
        m.event("lane_transition", lane=0, frm="healthy", to="suspect",
                reason=f"r{i}")
    m.event("breaker_trip", lane=1)
    snap = m.snapshot()
    assert snap["events_lane_transition"] == 6          # counter survives ring
    assert snap["events_breaker_trip"] == 1
    assert snap["events_total"] == 7 and snap["events_dropped"] == 3
    evs = m.events_for("lane_transition")
    assert len(evs) == 3                                # ring kept newest
    assert evs[-1].fields["reason"] == "r5"
    assert [e.seq for e in evs] == sorted(e.seq for e in evs)


def test_snapshot_is_consistent_under_concurrent_writers():
    """Counters bumped together must never tear apart in a snapshot: a
    writer increments a and b back to back under contention; every snapshot
    must see a >= b (a is bumped first) and both monotone."""
    m = MetricsRegistry()
    stop = threading.Event()

    def writer():
        while not stop.is_set():
            m.inc("a")
            m.inc("b")

    threads = [threading.Thread(target=writer) for _ in range(4)]
    for th in threads:
        th.start()
    last_a = last_b = 0
    try:
        for _ in range(300):
            snap = m.snapshot()
            assert snap["a"] >= snap["b"] >= 0
            assert snap["a"] >= last_a and snap["b"] >= last_b
            last_a, last_b = snap["a"], snap["b"]
    finally:
        stop.set()
        for th in threads:
            th.join()


def test_reset_zeroes_in_place_keeping_objects():
    m = MetricsRegistry()
    c = m.counter("x")
    h = m.histogram("lat", DEPTH_BUCKETS)
    m.inc("x", 3)
    m.observe("lat", 2.0, DEPTH_BUCKETS)
    m.event("detector", kind="ecc")
    m.reset()
    snap = m.snapshot()
    assert snap["x"] == 0 and snap["lat_count"] == 0
    assert snap["events_total"] == 0 and snap["events_detector"] == 0
    assert m.counter("x") is c and m.histogram("lat", DEPTH_BUCKETS) is h
    m.inc("x")
    assert c.value == 1                                 # old handle still live


# ----------------------------------------------------------------- exporters
def test_jsonl_roundtrip_and_canonical_projection(tmp_path):
    t = Tracer()
    with t.span("forward", "system", trace="t0", meta={"impl": "py"}):
        t.emit("image", "accel", attrs={"cycles": 11})
    path = str(tmp_path / "dump" / "run.trace.jsonl")
    assert texport.write_jsonl(t, path) == 2
    back = texport.read_jsonl(path)
    assert [d["name"] for d in back] == ["forward", "image"]
    assert back[0]["meta"] == {"impl": "py"}
    assert texport.canonical_lines(path) == t.canonical()
    with open(path) as f:                               # one object per line
        assert all(json.loads(line) for line in f)


def test_prometheus_exposition_format():
    m = MetricsRegistry()
    m.inc("lane_faults", 3)
    m.set_gauge("queue_depth_peak", 7)
    m.histogram("lat", (1.0, 10.0))
    for v in (0.5, 5.0, 50.0):
        m.observe("lat", v, (1.0, 10.0))
    text = texport.prometheus_text(m, prefix="repro")
    lines = text.strip().splitlines()
    assert "# TYPE repro_lane_faults counter" in lines
    assert "repro_lane_faults 3" in lines
    assert "# TYPE repro_queue_depth_peak gauge" in lines
    assert "# TYPE repro_lat histogram" in lines
    assert 'repro_lat_bucket{le="1.0"} 1' in lines      # cumulative
    assert 'repro_lat_bucket{le="10.0"} 2' in lines
    assert 'repro_lat_bucket{le="+Inf"} 3' in lines
    assert "repro_lat_count 3" in lines
    assert text.endswith("\n")


# ------------------------------------------- end-to-end determinism (boards)
def _traced_forward(art, spec, images):
    from repro.core.runtimes import make_runtime
    t = Tracer()
    prev = ttrace.install(t)
    try:
        rt = make_runtime(art, spec)
        rt.forward(images)
    finally:
        ttrace.install(prev)
    return t


def test_board_span_tree_seeded_runs_bit_identical(trained_artifact):
    art, _, (xte, _) = trained_artifact
    t1 = _traced_forward(art, "board", xte[:4])
    t2 = _traced_forward(art, "board", xte[:4])
    assert t1.fingerprint() == t2.fingerprint()
    assert t1.canonical() == t2.canonical()


def test_board_py_and_batched_span_trees_agree(trained_artifact):
    """The batched fast path must project the SAME canonical span tree as
    the per-image scheduler — impl differences live in meta only."""
    art, _, (xte, _) = trained_artifact
    tp = _traced_forward(art, "board-py", xte[:4])
    tb = _traced_forward(art, "board-batched", xte[:4])
    assert tp.canonical() == tb.canonical()
    assert tp.fingerprint() == tb.fingerprint()
    names = [s.name for s in tp.sorted_spans() if s.name == "board.image"]
    assert len(names) == 4                              # one span per image
    impls = {s.meta.get("impl") for s in tp.sorted_spans()
             if s.name == "board.forward"}
    assert impls != {s.meta.get("impl") for s in tb.sorted_spans()
                     if s.name == "board.forward"}


def test_board_image_spans_carry_logical_clocks(trained_artifact):
    art, _, (xte, _) = trained_artifact
    t = _traced_forward(art, "board", xte[:3])
    run = t.find("board.run")[0]
    imgs = t.find("board.image")
    assert run.scope == "accel"
    for s in imgs:
        assert s.scope == "accel"
        assert s.parent == run.sid and s.trace == run.trace
        assert s.attrs["cycles"] > 0 and s.attrs["events"] > 0
    assert sum(s.attrs["cycles"] for s in imgs) == run.attrs["cycles"]


# ------------------------------------------------- scheduler span determinism
def test_scheduler_inline_spans_deterministic_and_causal(trained_artifact):
    art, _, (xte, _) = trained_artifact
    from repro.serving.scheduler import ServingScheduler

    def run():
        t = Tracer()
        prev = ttrace.install(t)
        try:
            s = ServingScheduler(art, spec="accelerator-event",
                                 kernel="fused", max_batch=4)
            rids = [s.submit(x) for x in xte[:6]]
            done = s.drain()
        finally:
            ttrace.install(prev)
        return t, rids, done

    t1, rids, done = run()
    t2, _, _ = run()
    assert t1.fingerprint() == t2.fingerprint()

    # request tree: request -> admission / complete (the batch that served
    # it is named by the batch span's rids, not by a per-request span)
    req = t1.traces()[f"req-{rids[0]:08d}"]
    root = req[0]
    assert root.name == "request" and root.parent is None
    kids = [s.name for s in req if s.parent == root.sid]
    assert kids == ["admission", "complete"]
    comp = next(s for s in req if s.name == "complete")
    assert comp.attrs["label"] == int(done[rids[0]].label)

    # batch tree: batch -> runtime -> accel.forward -> accel.dispatch, with
    # the lane's id and health on the batch span itself
    batches = t1.roots("batch")
    assert len(batches) == 2                            # 4 + 2
    assert batches[0].meta["lane"] == 0
    assert batches[0].meta["health"] == "healthy"
    assert rids[0] in batches[0].meta["rids"]
    assert "lane" not in {s.name for s in t1.sorted_spans()}
    runtime = next(s for s in t1.children(batches[0]) if s.name == "runtime")
    fwd = next(s for s in t1.children(runtime) if s.name == "accel.forward")
    assert fwd.scope == "system"
    assert any(s.name == "accel.dispatch" and s.scope == "accel"
               for s in t1.children(fwd))
    assert not t1.find("accel.kernel")


# ----------------------------------------------- phase spans of a served batch
# (name, parent name) of every span in one served batch's trace, in record
# order: the lane's wait for a first request and the batch's formation come
# before the batch opens, the completion loop after it closes
SERVED_BATCH_TREE = [
    ("lane.idle", None), ("batch.form", None), ("batch", None),
    ("lane.pad", "batch"), ("runtime", "batch"),
    ("lane.encode", "runtime"),
    ("accel.forward", "runtime"), ("accel.dispatch", "accel.forward"),
    ("lane.device_wait", "runtime"), ("lane.readback", "runtime"),
    ("batch.complete", None)]
# the lane's phases inside its runtime call
RUNTIME_PHASES = ("lane.encode", "accel.dispatch", "lane.device_wait",
                  "lane.readback", "lane.reroute")


def _serve_one_at_a_time(art, images, tracer):
    """Serve ``images`` through one worker lane, one request per batch, the
    lane idle before each; returns ({rid: trace of the batch that served
    it}, the scheduler's stats)."""
    from repro.serving.scheduler import ServingScheduler
    prev = ttrace.install(tracer)
    try:
        with ServingScheduler(art, spec="accelerator-event", kernel="fused",
                              workers=1, max_wait_us=1000) as s:
            for x in images:
                time.sleep(0.05)            # the lane is back waiting
                s.result(s.submit(x), timeout=60)
            stats = s.stats()
    finally:
        ttrace.install(prev)
    return {rid: b.trace for b in tracer.roots("batch")
            for rid in b.meta["rids"]}, stats


def _tree(tracer, trace):
    spans = tracer.traces()[trace]
    name = {s.sid: s.name for s in spans}
    return [(s.name, name.get(s.parent)) for s in spans]


@pytest.mark.parametrize("overflow", [False, True],
                         ids=["event_path", "dense_reroute"])
def test_served_batch_records_every_phase_span(trained_artifact, overflow):
    art, _, (xte, _) = trained_artifact
    if overflow:
        art = tiny_emax_artifact(art)
    t = Tracer()
    batches, stats = _serve_one_at_a_time(art, xte[:2], t)
    assert len(batches) == 2
    want = list(SERVED_BATCH_TREE)
    if overflow:       # the row overflows E_max = 8: the dense forward
        at = want.index(("lane.readback", "runtime")) + 1
        want[at:at] = [("lane.reroute", "runtime"),
                       ("accel.forward", "lane.reroute"),
                       ("accel.dispatch", "accel.forward")]
    # the second batch: the lane was idle with an empty queue before it
    assert _tree(t, batches[1]) == want
    spans = {}
    for s in t.traces()[batches[1]]:
        spans.setdefault(s.name, s)
    # accel.forward stays a direct child of runtime (benchmark readers key
    # it by (trace, parent))
    assert spans["accel.forward"].parent == spans["runtime"].sid
    assert spans["lane.readback"].attrs["events"] > 0
    assert stats["mean_events"] > 0
    if overflow:
        assert spans["lane.reroute"].attrs == {"rows": 1}
    # phases follow one another: nothing of the batch overlaps its wait
    order = [spans[n] for n in ("lane.idle", "batch.form", "batch",
                                "batch.complete")]
    for a, b in zip(order, order[1:]):
        assert a.wall_ns_end <= b.wall_ns_start


def test_phase_spans_cover_the_runtime_call(trained_artifact):
    """The lane's phase spans leave at most 5% of each runtime call's wall
    time unaccounted for."""
    art, _, (xte, _) = trained_artifact
    t = Tracer()
    _serve_one_at_a_time(art, xte[:4], t)
    runtimes = t.find("runtime")
    assert len(runtimes) == 4
    for r in runtimes:
        phases = sorted((s.wall_ns_start, s.wall_ns_end)
                        for s in t.traces()[r.trace]
                        if s.name in RUNTIME_PHASES)
        covered, end = 0, r.wall_ns_start
        for lo, hi in phases:
            lo, hi = max(lo, end), min(hi, r.wall_ns_end)
            if hi > lo:
                covered += hi - lo
                end = hi
        assert covered >= 0.95 * (r.wall_ns_end - r.wall_ns_start)


def test_cpu_time_only_on_context_spans_and_within_wall(trained_artifact):
    art, _, (xte, _) = trained_artifact
    t = Tracer()
    _serve_one_at_a_time(art, xte[:2], t)
    context = {name for name, _ in SERVED_BATCH_TREE} - {"batch"}
    spans = t.sorted_spans()
    for s in spans:
        if s.name in context:
            assert s.cpu_ns is not None and s.cpu_ns >= 0, s.name
            assert s.cpu_ns <= s.wall_ns_end - s.wall_ns_start, s.name
            assert s.full()["cpu_ns"] == s.cpu_ns
        elif s.name in ("batch", "request", "admission", "complete"):
            assert s.cpu_ns is None               # begin/end or emitted
    # a span that computes spends CPU time
    busy = Tracer()
    with busy.span("spin", "system"):
        x = 0
        for i in range(200_000):
            x += i
    (spin,) = busy.sorted_spans()
    assert 0 < spin.cpu_ns <= spin.wall_ns_end - spin.wall_ns_start


def test_serving_without_a_tracer_records_nothing(trained_artifact,
                                                  monkeypatch):
    """With the NullRecorder installed the served path makes the parent's
    calls: one device wait per batch, no profiler annotation, no CPU clock
    read, no trace id, no span handle on a request."""
    import jax

    from repro.serving.scheduler import ServingScheduler
    art, _, (xte, _) = trained_artifact
    assert isinstance(ttrace.get(), NullRecorder)
    waits = []
    real_wait = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: waits.append(1) or real_wait(x))

    def forbidden(*a, **kw):
        raise AssertionError("tracing work on the untraced path")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", forbidden)
    monkeypatch.setattr(time, "thread_time_ns", forbidden)
    monkeypatch.setattr(Tracer, "_record", forbidden)
    s = ServingScheduler(art, spec="accelerator-event", kernel="fused",
                         max_batch=4)
    waits.clear()                         # construction warms the lane
    rids = [s.submit(x) for x in xte[:6]]
    reqs = [s._requests[r] for r in rids]
    done = s.drain()
    assert sorted(done) == rids
    assert len(waits) == 2                # 4 + 2: one device wait per batch
    assert s._batch_seq == 0
    assert all(r._span is None and r._adm is None for r in reqs)


def test_phase_annotations_share_the_profiler_clock(tmp_path):
    """A context-managed span is mirrored on the profiler's host plane; the
    benchmark's one-anchor mapping (perf_counter_ns read just before an
    anchor annotation opens) puts the span within 1 ms of its mirror."""
    import jax

    from benchmarks.chip import devtrace
    t = Tracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        anchor_ns = time.perf_counter_ns()
        with jax.profiler.TraceAnnotation(devtrace.ANCHOR):
            time.sleep(0.01)
            with t.span("lane.encode", "system"):
                jax.numpy.arange(8).sum().block_until_ready()
                time.sleep(0.005)
    finally:
        jax.profiler.stop_trace()
    tr = devtrace.load_xplane(str(tmp_path))
    offset = tr.anchor[0] - anchor_ns
    from jax.profiler import ProfileData
    (path,) = tmp_path.rglob("*.xplane.pb")
    mirrored = [e for plane in ProfileData.from_file(str(path)).planes
                if plane.name.startswith("/host")
                for line in plane.lines for e in line.events
                if e.name == "lane.encode"]
    assert len(mirrored) == 1
    (span,) = t.sorted_spans()
    assert abs(mirrored[0].start_ns - (span.wall_ns_start + offset)) < 1e6
    assert abs(mirrored[0].end_ns - (span.wall_ns_end + offset)) < 1e6
    assert span.wall_ns_end - span.wall_ns_start >= 5e6
