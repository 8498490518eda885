"""Continuous-batching scheduler: batch formation (size and deadline close),
worker lanes, latency percentiles, result()/drain() APIs, and the
single-code-path overflow reroute / board accounting."""

import threading
import time

import numpy as np
import pytest

from repro.core.reference import SNNReference
from repro.serving.scheduler import ServingError, ServingScheduler

from _fakes import broken_family, tiny_emax_artifact


def test_inline_mode_greedy_deterministic_batches(trained_artifact):
    art, _, (xte, _) = trained_artifact
    s = ServingScheduler(art, spec="accelerator-event", kernel="fused",
                         max_batch=4)
    rids = [s.submit(x) for x in xte[:10]]
    done = s.drain()
    assert sorted(done) == rids
    st = s.stats()
    assert st["batches"] == 3 and st["images_out"] == 10   # 4 + 4 + 2
    assert st["batch_fill_mean"] == pytest.approx(10 / 3)
    assert st["system_s"] >= st["accelerator_s"] > 0
    assert s.drain() == {}                                 # queue drained


def test_threaded_lanes_bitexact_with_reference(trained_artifact):
    """Labels served through 2 continuous-batching lanes (whatever batches
    form) are bit-exact with the reference — padding and batch composition
    must not change an answer."""
    art, _, (xte, _) = trained_artifact
    want = np.asarray(SNNReference(art).forward(xte[:48]).labels)
    with ServingScheduler(art, spec="accelerator-event", kernel="fused",
                          workers=2, max_batch=8, max_wait_us=500.0) as s:
        rids = [s.submit(x) for x in xte[:48]]
        done = s.drain()
        got = np.asarray([done[r].label for r in rids])
        assert np.array_equal(got, want)
        assert {done[r].lane for r in rids} <= {0, 1}
        st = s.stats()
        assert (0 < st["p50_latency_us"] <= st["p95_latency_us"]
                <= st["p99_latency_us"])
        assert st["queue_depth_peak"] >= 0
        assert st["images_out"] == 48


def test_deadline_closes_partial_batch(trained_artifact):
    """Under light load a batch must close at max_wait_us, not wait for
    max_batch requests that will never come."""
    art, _, (xte, _) = trained_artifact
    with ServingScheduler(art, spec="accelerator-event", kernel="fused",
                          workers=1, max_batch=64, max_wait_us=1000.0) as s:
        req = s.result(s.submit(xte[0]), timeout=120.0)
        assert req.label is not None and req.lane == 0
        st = s.stats()
        assert st["batches"] == 1
        assert st["batch_fill_mean"] <= 2                  # closed near-empty


def test_closed_loop_result_api(trained_artifact):
    """Concurrent closed-loop clients each block on their own request."""
    art, _, (xte, _) = trained_artifact
    want = np.asarray(SNNReference(art).forward(xte[:24]).labels)
    errs = []
    with ServingScheduler(art, spec="accelerator-event", kernel="fused",
                          workers=2, max_batch=8, max_wait_us=500.0) as s:
        def client(c):
            for i in range(c, 24, 3):
                r = s.result(s.submit(xte[i]), timeout=120.0)
                if r.label != want[i]:
                    errs.append((i, r.label))
        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert errs == []
        assert s.stats()["images_out"] == 24
    with pytest.raises(RuntimeError, match="closed"):
        s.submit(xte[0])


def test_overflow_reroute_lives_in_scheduler(trained_artifact):
    """The overflow→dense reroute is scheduler-side: rows beyond E_max are
    served through the dense path in ANY mode, labels still exact."""
    art, _, (xte, _) = trained_artifact
    tiny = tiny_emax_artifact(art, e_max=8)
    want = np.asarray(SNNReference(art).forward(xte[:24]).labels)
    with ServingScheduler(tiny, spec="accelerator-event", kernel="fused",
                          workers=1, max_batch=8, max_wait_us=500.0) as s:
        rids = [s.submit(x) for x in xte[:24]]
        done = s.drain()
        got = np.asarray([done[r].label for r in rids])
        assert np.array_equal(got, want)
        st = s.stats()
        assert st["overflow_fallbacks"] > 0
        assert any(done[r].fallback_dense for r in rids)


def test_device_flagged_rows_and_only_those_take_the_dense_reroute(
        trained_artifact):
    """The overflow flags the device packer returns route exactly the rows
    whose events exceed E_max through the dense path, with the dense path's
    labels and steps; every other row keeps the event path's early exit."""
    import jax.numpy as jnp

    from repro.core import events, ttfs
    art, _, (xte, _) = trained_artifact
    tiny = tiny_emax_artifact(art, e_max=8)
    T = int(art.m("encode", "T"))
    x_min = float(art.m("encode", "x_min"))
    images = np.array(xte[:12])
    for row in images[::2]:              # every other row: 6 events at most
        keep = np.flatnonzero(row >= x_min)[:6]
        sparse = np.zeros_like(row)
        sparse[keep] = row[keep]
        row[:] = sparse
    times = np.asarray(ttfs.encode_ttfs(jnp.asarray(images), T, x_min))
    over = np.asarray(events.pack_events_batched(times, T, 8).overflow)
    assert 0 < over.sum() < len(images)
    ref = SNNReference(art).forward(images)
    first = np.asarray(ref.first_spike).min(axis=1)
    want_steps = np.where(over, T, np.where(first < T, first + 1, T))
    with ServingScheduler(tiny, spec="accelerator-event", kernel="fused",
                          max_batch=8, latency_mode=True) as s:
        rids = [s.submit(x) for x in images]
        done = s.drain()
        st = s.stats()
    assert [done[r].fallback_dense for r in rids] == list(over)
    assert np.array_equal([done[r].label for r in rids],
                          np.asarray(ref.labels))
    assert np.array_equal([done[r].steps for r in rids], want_steps)
    assert st["overflow_fallbacks"] == int(over.sum())
    assert st["mean_events"] == pytest.approx(
        np.count_nonzero(times < T) / len(images))


def test_board_accounting_and_denominators(trained_artifact):
    art, _, (xte, _) = trained_artifact
    s = ServingScheduler(art, spec="board-batched", max_batch=16)
    # empty stats: every per-image rate uses the SAME zero-traffic guard
    st0 = s.stats()
    assert st0["accel_us_per_image"] == 0.0
    assert st0["board_model_us_per_image"] == 0.0
    assert st0["board_nj_per_image"] == 0.0
    rids = [s.submit(x) for x in xte[:20]]
    done = s.drain()
    want = np.asarray(SNNReference(art).forward(xte[:20]).labels)
    assert np.array_equal(np.asarray([done[r].label for r in rids]), want)
    st = s.stats()
    assert st["board_cycles"] > 0 and st["board_nj_per_image"] > 0
    clock = s.lanes[0].runtime.cost.clock_hz
    assert st["board_model_us_per_image"] == pytest.approx(
        1e6 * st["board_cycles_per_image"] / clock)
    assert st["overflow_fallbacks"] == 0   # board backpressures, never drops


def test_malformed_image_rejected_at_admission(trained_artifact):
    """A bad shape must never reach a lane where it would poison a whole
    batch — submit() rejects it synchronously."""
    art, _, _ = trained_artifact
    s = ServingScheduler(art, spec="accelerator-event", kernel="fused",
                         max_batch=4)
    with pytest.raises(ValueError, match="shape"):
        s.submit(np.zeros(3, np.float32))      # wrong width: (3,) vs (N_in,)
    assert s.drain() == {}                     # nothing was admitted


def test_failed_batch_never_strands_waiters(trained_artifact):
    """A worker-lane exception mid-batch must not vanish: the request
    completes with .error set, result() raises a descriptive ServingError,
    drain()/result() never hang, and later traffic is still served (the
    lane is scrubbed and rebuilt). Inline mode re-raises to the synchronous
    caller after error-completing the batch."""
    art, _, (xte, _) = trained_artifact

    def boom(images, k, probe=False):
        raise RuntimeError("injected mid-batch explosion")

    with ServingScheduler(art, spec="accelerator-event", kernel="fused",
                          workers=1, max_batch=4, max_wait_us=500.0,
                          resilience={"max_retries": 0, "backoff_s": 0.001},
                          ) as s:
        s.lanes[0].serve = boom                # this lane throws mid-batch
        rid = s.submit(xte[0])
        with pytest.raises(ServingError, match="explosion") as ei:
            s.result(rid, timeout=120.0)       # raises instead of hanging
        req = ei.value.request
        assert req.rid == rid and req.label is None
        assert "injected mid-batch explosion" in req.error
        st = s.stats()
        assert st["errors"] == 1 and st["lane_faults"] >= 1
        ok = s.result(s.submit(xte[0]), timeout=120.0)   # rebuilt lane serves
        assert ok.error is None and ok.label is not None
        assert s.stats()["lane_restarts"] >= 1

    s2 = ServingScheduler(art, spec="accelerator-event", kernel="fused",
                          max_batch=4)
    s2.lanes[0].serve = boom
    rid2 = s2.submit(xte[0])
    with pytest.raises(RuntimeError, match="explosion"):
        s2.drain()                             # inline mode surfaces it
    done = s2.drain()                          # ...but nothing is stranded
    assert done[rid2].error is not None and s2.stats()["errors"] == 1


def test_drain_does_not_steal_claimed_result(trained_artifact):
    """A rid a result() caller is blocked on must not be swept up by a
    concurrent drain() — the claim protects it."""
    art, _, (xte, _) = trained_artifact
    with ServingScheduler(art, spec="accelerator-event", kernel="fused",
                          workers=1, max_batch=4, max_wait_us=500.0) as s:
        got = {}
        rid = s.submit(xte[0])
        t = threading.Thread(
            target=lambda: got.update(r=s.result(rid, timeout=120.0)))
        t.start()
        deadline = time.time() + 30
        while rid not in s._claims:            # wait for the claim to land
            assert time.time() < deadline
            time.sleep(0.001)
        drained = s.drain()
        t.join(timeout=120.0)
        assert not t.is_alive()
        assert got["r"].rid == rid and got["r"].label is not None
        assert rid not in drained


def test_close_fails_backlog_instead_of_draining_it(trained_artifact):
    """close() finishes the batch in flight but does NOT serve the backlog:
    unserved requests complete with error='scheduler closed'."""
    art, _, (xte, _) = trained_artifact
    s = ServingScheduler(art, spec="accelerator-event", kernel="fused",
                         workers=1, max_batch=4, max_wait_us=10_000_000.0)
    rids = [s.submit(x) for x in xte[:64]]     # far more than one batch
    s.close()
    done = s.drain()
    assert sorted(done) == rids
    failed = [r for r in done.values() if r.error == "scheduler closed"]
    served = [r for r in done.values() if r.error is None]
    assert len(failed) + len(served) == 64 and failed


def test_result_unknown_or_already_claimed_rid_raises(trained_artifact):
    """result() on a rid that is neither outstanding nor completed fails
    loudly (KeyError) instead of blocking forever — the already-drained /
    already-returned / never-submitted cases."""
    art, _, (xte, _) = trained_artifact
    s = ServingScheduler(art, spec="accelerator-event", kernel="fused",
                         max_batch=4)
    with pytest.raises(KeyError):
        s.result(999)                          # never submitted
    rid = s.result(s.submit(xte[0]), timeout=120.0).rid
    with pytest.raises(KeyError):
        s.result(rid)                          # already returned
    rid2 = s.submit(xte[1])
    s.drain()
    with pytest.raises(KeyError):
        s.result(rid2)                         # swept by a drain()


def test_stats_snapshot_consistent_under_concurrent_chaos(trained_artifact):
    """stats() is one consistent registry snapshot, not a field-by-field
    read of live counters: submitter threads and a crashing lane mutate the
    account while readers hammer stats(). Every successive snapshot must be
    monotone in the counter totals, never show more completions than
    admissions, and the final account must be exact."""
    art, _, (xte, _) = trained_artifact
    n, n_threads = 48, 3
    s = ServingScheduler(art, spec="accelerator-event", kernel="fused",
                         workers=2, max_batch=8, max_wait_us=500.0,
                         faults="crash=0,seed=12",
                         resilience={"backoff_s": 0.001})
    submitted = []
    sub_lock = threading.Lock()
    stop = threading.Event()
    violations: list[str] = []

    def submitter(k):
        for i in range(k, n, n_threads):
            rid = s.submit(xte[i % len(xte)])
            with sub_lock:
                submitted.append(rid)

    def reader():
        monotone = ("images_out", "batches", "requeued", "lane_faults",
                    "lane_restarts", "errors")
        last = {k: 0 for k in monotone}
        while not stop.is_set():
            st = s.stats()
            with sub_lock:
                n_sub = len(submitted)
            if st["images_out"] > n_sub:
                violations.append(f"torn read: images_out "
                                  f"{st['images_out']} > submitted {n_sub}")
            for k in monotone:
                if st[k] < last[k]:
                    violations.append(f"counter {k} went backwards: "
                                      f"{st[k]} < {last[k]}")
                last[k] = st[k]
            if st["batches"] and st["images_out"] < st["batches"]:
                violations.append("more batches than completed images")

    with s:
        readers = [threading.Thread(target=reader) for _ in range(2)]
        subs = [threading.Thread(target=submitter, args=(k,))
                for k in range(n_threads)]
        for t in readers + subs:
            t.start()
        for t in subs:
            t.join(timeout=120.0)
        done = s.drain()
        stop.set()
        for t in readers:
            t.join(timeout=30.0)
        st = s.stats()
    assert not violations, violations[:5]
    assert sorted(done) == sorted(submitted)
    assert st["images_out"] == n and st["lane_faults"] >= 1
    assert all(r.error is None for r in done.values())


@pytest.mark.parametrize("workers", [0, 1])
def test_lane_whose_warmup_raises_fails_construction(trained_artifact,
                                                      monkeypatch, workers):
    """No fault plan, so nothing injected can explain a warm-up probe that
    raises: the program does not run. Construction must raise that error —
    never quarantine the lane and quietly serve the dense path instead."""
    art, _, _ = trained_artifact
    degraded = []
    monkeypatch.setattr(ServingScheduler, "_degrade",
                        lambda self, lane: degraded.append(lane.lane_id))
    with broken_family():
        with pytest.raises(RuntimeError, match="does not run on this device"):
            ServingScheduler(art, spec="broken", workers=workers,
                             max_batch=4)
    assert degraded == []
