"""Load generators: a closed loop and an open Poisson loop, both driving the
scheduler through ``submit()`` and ``result()`` only.

Every request is recorded with the pool image it carried, when it was due,
when it was submitted, and when the scheduler completed it (the request's own
``t_done``, on the same ``time.perf_counter`` clock), with its answer.
"""

from __future__ import annotations

import collections
import dataclasses
import queue
import threading
import time

import numpy as np

# How long after the window closes the collector waits for one request.
LATE_S = 60.0


@dataclasses.dataclass
class Records:
    """One row per request the window offered, in submission order."""
    image: np.ndarray    # pool index of the request's image
    due: np.ndarray      # perf_counter seconds the request was due
    submit: np.ndarray   # perf_counter seconds it was submitted
    done: np.ndarray     # perf_counter seconds it completed (nan: never)
    label: np.ndarray    # served label (-1: none)
    steps: np.ndarray    # served step count (-1: none)
    error: np.ndarray    # completed with an error, or never completed

    @classmethod
    def empty(cls, n: int) -> "Records":
        f = np.full(n, np.nan)
        i = np.full(n, -1, np.int64)
        return cls(np.zeros(n, np.int64), f.copy(), f.copy(), f.copy(),
                   i.copy(), i.copy(), np.zeros(n, bool))

    def __len__(self) -> int:
        return len(self.image)


def _collect(sched, rec: Records, k: int, rid: int) -> None:
    """Wait for request ``rid`` and record its answer in row ``k``."""
    from repro.serving.scheduler import ServingError
    try:
        r = sched.result(rid, timeout=LATE_S)
    except ServingError as e:
        rec.error[k] = True
        rec.done[k] = e.request.t_done
        return
    except TimeoutError:
        rec.error[k] = True
        return
    rec.done[k] = r.t_done
    rec.label[k] = r.label
    rec.steps[k] = r.steps


def closed_loop(sched, images: np.ndarray, order: np.ndarray,
                outstanding: int, seconds: float) -> tuple[Records, float]:
    """Keep ``outstanding`` requests in flight for ``seconds``: one client
    thread waits for the oldest request and submits the next as it returns.
    With one lane, batches form first in first out, so the oldest request is
    always among the first to complete. Returns (records, window start)."""
    submitted: list[tuple[int, float, int]] = []    # (image, time, rid)
    inflight: collections.deque = collections.deque()

    def submit() -> None:
        idx = int(order[len(submitted) % len(order)])
        now = time.perf_counter()
        inflight.append(len(submitted))
        submitted.append((idx, now, sched.submit(images[idx])))

    t0 = time.perf_counter()
    for _ in range(outstanding):
        submit()
    rec = Records.empty(1 << 20)
    while inflight:
        k = inflight.popleft()
        _collect(sched, rec, k, submitted[k][2])
        if time.perf_counter() < t0 + seconds:
            submit()
    n = len(submitted)
    if n > len(rec):
        raise RuntimeError(f"{n} requests overflow the closed loop's record")
    rec = Records(*(getattr(rec, f.name)[:n]
                    for f in dataclasses.fields(rec)))
    rec.image[:] = [s[0] for s in submitted]
    rec.due[:] = rec.submit[:] = [s[1] for s in submitted]
    return rec, t0


def open_loop(sched, images: np.ndarray, order: np.ndarray,
              schedule: np.ndarray) -> tuple[Records, float]:
    """Submit request ``k`` at window start + ``schedule[k]`` from a thread
    of its own, whether or not earlier requests have completed; the calling
    thread collects answers in submission order. Returns (records, start)."""
    n = len(schedule)
    rec = Records.empty(n)
    handoff: queue.SimpleQueue = queue.SimpleQueue()
    t0 = time.perf_counter() + 0.01
    rec.due[:] = t0 + schedule
    rec.image[:] = order[np.arange(n) % len(order)]

    def generate() -> None:
        try:
            for k in range(n):
                wait = rec.due[k] - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                rid = sched.submit(images[rec.image[k]])
                rec.submit[k] = time.perf_counter()
                handoff.put((k, rid))
        finally:
            handoff.put(None)

    gen = threading.Thread(target=generate, name="bench-generator",
                           daemon=True)
    gen.start()
    try:
        while (item := handoff.get()) is not None:
            _collect(sched, rec, *item)
    finally:
        gen.join(timeout=LATE_S)
    if gen.is_alive():
        raise RuntimeError("the load generator did not finish")
    rec.error |= np.isnan(rec.submit)
    return rec, t0
