"""Batched SNN serving engine: queueing, micro-batching, overflow fallback,
scope-aware stats — plus the event-path edge cases the engine relies on."""

import numpy as np
import pytest

from repro.core import events
from repro.core.accelerator import SNNAccelerator
from repro.core.reference import SNNReference
from repro.serving.snn_engine import SNNServeEngine

from _fakes import tiny_emax_artifact


# ----------------------------------------------------------------- serving
def test_engine_matches_reference_labels(trained_artifact):
    art, _, (xte, yte) = trained_artifact
    ref = SNNReference(art)
    want = np.asarray(ref.forward(xte[:96]).labels)
    for kernel in ("jnp", "fused"):
        eng = SNNServeEngine(art, max_batch=32, kernel=kernel)
        got = eng.classify(xte[:96])
        assert np.array_equal(got, want), kernel


def test_engine_micro_batches_and_stats(trained_artifact):
    art, _, (xte, _) = trained_artifact
    eng = SNNServeEngine(art, max_batch=4, kernel="fused")
    rids = [eng.submit(x) for x in xte[:10]]
    done = eng.flush()
    assert sorted(done) == rids
    assert all(done[r].label is not None for r in rids)
    st = eng.stats()
    assert st["images_out"] == 10
    assert st["batches"] == 3                      # 4 + 4 + 2 (padded)
    assert st["system_s"] >= st["accelerator_s"] > 0
    assert st["host_overhead_s"] >= 0
    assert st["overflow_fallbacks"] == 0


def test_engine_latency_mode_matches_full(trained_artifact):
    art, _, (xte, _) = trained_artifact
    full = SNNServeEngine(art, max_batch=16, kernel="fused")
    lat = SNNServeEngine(art, max_batch=16, kernel="fused", latency_mode=True)
    want = full.classify(xte[:32])
    got = lat.classify(xte[:32])
    assert np.array_equal(got, want)
    T = int(art.m("encode", "T"))
    done = lat.flush()                             # empty queue -> no-op
    assert done == {}
    rid = lat.submit(xte[0])
    steps = lat.flush()[rid].steps
    assert 0 < steps <= T


def test_engine_overflow_falls_back_to_dense(trained_artifact):
    """Rows whose frames exceed E_max must be served via the dense batch
    path, not dropped — labels still match the reference exactly."""
    art, _, (xte, _) = trained_artifact
    tiny = tiny_emax_artifact(art, e_max=8)
    eng = SNNServeEngine(tiny, max_batch=16, kernel="fused")
    got = eng.classify(xte[:32])
    want = np.asarray(SNNReference(art).forward(xte[:32]).labels)
    assert np.array_equal(got, want)
    st = eng.stats()
    assert st["overflow_fallbacks"] > 0
    done_flags = [r.fallback_dense for r in eng.flush().values()]
    assert done_flags == []                        # queue drained


def test_engine_board_backend_honors_kernel(trained_artifact):
    """backend="board" used to silently drop kernel= (a requested Pallas
    board path quietly ran jnp); the requested kernel must be the one
    constructed — and an impossible one must fail loudly."""
    art, _, _ = trained_artifact
    assert SNNServeEngine(art, backend="board").accel.kernel == "jnp"
    eng = SNNServeEngine(art, backend="board", kernel="pallas")
    assert eng.accel.kernel == "pallas"
    with pytest.raises(ValueError, match="accelerator-family"):
        SNNServeEngine(art, backend="board", kernel="fused")
    # accelerator backend: kernel=None means its own default, "fused"
    assert SNNServeEngine(art).accel.kernel == "fused"
    assert SNNServeEngine(art, kernel="jnp").accel.kernel == "jnp"


def test_classify_preserves_unclaimed_submits(trained_artifact):
    """classify() drains the whole queue but must NOT discard results of
    requests submit()ed earlier by other callers — they stay claimable by
    the next flush()."""
    art, _, (xte, _) = trained_artifact
    ref = SNNReference(art)
    eng = SNNServeEngine(art, max_batch=8, kernel="fused")
    rid_early = eng.submit(xte[0])
    got = eng.classify(xte[1:5])
    want = np.asarray(ref.forward(xte[:5]).labels)
    assert np.array_equal(got, want[1:5])      # classify sees only its own
    done = eng.flush()                         # earlier submit still claimable
    assert list(done) == [rid_early]
    assert done[rid_early].label == want[0]
    assert eng.flush() == {}                   # claimed exactly once


def test_engine_stats_percentiles_and_workers(trained_artifact):
    """The facade surfaces the scheduler's latency percentiles, and
    workers>=1 turns on continuous batching behind the same API."""
    art, _, (xte, _) = trained_artifact
    eng = SNNServeEngine(art, max_batch=8, kernel="fused")
    eng.classify(xte[:16])
    st = eng.stats()
    assert (0 < st["p50_latency_us"] <= st["p95_latency_us"]
            <= st["p99_latency_us"])
    assert st["backend"] == "accelerator" and st["workers"] == 0

    want = np.asarray(SNNReference(art).forward(xte[:16]).labels)
    eng2 = SNNServeEngine(art, max_batch=8, kernel="fused", workers=2,
                          max_wait_us=500.0)
    try:
        assert np.array_equal(eng2.classify(xte[:16]), want)
        assert eng2.stats()["workers"] == 2
    finally:
        eng2.close()


# ------------------------------------------------------- event path edges
def test_accelerator_overflow_raises_and_opt_out(trained_artifact):
    art, _, (xte, _) = trained_artifact
    tiny = tiny_emax_artifact(art, e_max=8)
    acc = SNNAccelerator(tiny, mode="event", kernel="fused")
    with pytest.raises(OverflowError):
        acc.forward(xte[:8])
    # pre-validated callers may skip the host overflow read; the forward
    # then runs on the (deterministically truncated) frames without raising
    out = acc.forward(xte[:8], check_overflow=False)
    assert out.labels.shape == (8,)


def test_calibrate_e_max_headroom_and_rounding():
    times = np.zeros((2, 100), np.int32)           # 100 events at t=0
    e = events.calibrate_e_max(times, T=4, lane=128)
    assert e == 128                                # rounded up to one lane
    e2 = events.calibrate_e_max(times, T=4, lane=128, headroom=1.5)
    assert e2 == 256                               # 150 -> two lanes
    assert events.calibrate_e_max(times, T=4, lane=8) == 104  # 100 -> 8*13


def test_packing_vectorized_equals_loop_large():
    """The bincount/cumsum packer agrees with the O(B*T) loop packer on a
    big ragged case (the host 'spike packing' stage of the system path)."""
    rng = np.random.RandomState(3)
    times = rng.randint(0, 33, (16, 784)).astype(np.int32)
    a = events.pack_events(times, 32, 128)
    b = events.pack_events_batched(times, 32, 128)
    assert np.array_equal(np.asarray(a.ids), np.asarray(b.ids))
    assert np.array_equal(np.asarray(a.count), np.asarray(b.count))
    assert np.array_equal(np.asarray(a.overflow), np.asarray(b.overflow))
