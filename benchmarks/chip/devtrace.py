"""Reduction of a profiler trace to device busy time, idle share, kernel
time, top device operations, and idle gaps attributed to what the host was
doing.

All times are nanoseconds on the trace's clock. The program's host spans
(``repro.telemetry`` Tracer, on ``time.perf_counter_ns``) are moved onto
that clock by an anchor: a ``jax.profiler.TraceAnnotation`` named
``ANCHOR`` that the benchmark opens around its timed window, with the
``perf_counter_ns`` read just before it opens.
"""

from __future__ import annotations

import dataclasses
import glob
import json
import os
import re
from pathlib import Path

ANCHOR = "chipbench.window"
KERNELS = Path(__file__).resolve().parent / "kernels.json"


@dataclasses.dataclass
class Trace:
    ops: list[tuple[str, float, float]]   # (name, start, end) device ops
    n_devices: int
    anchor: tuple[float, float]           # the timed window, trace clock


def load_xplane(logdir: str) -> Trace:
    """Read the one ``.xplane.pb`` the profiler wrote under ``logdir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one xplane.pb under {logdir}, found "
                           f"{len(paths)}")
    return from_profile(ProfileData.from_file(paths[0]))


def device_planes(pd) -> list:
    return [p for p in pd.planes if re.fullmatch(r"/device:TPU:\d+", p.name)]


def from_profile(pd) -> Trace:
    ops, anchor, n = [], None, 0
    for plane in device_planes(pd):
        n += 1
        for line in plane.lines:
            if line.name == "XLA Ops":
                ops += [(e.name, e.start_ns, e.end_ns) for e in line.events]
    for plane in pd.planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == ANCHOR:
                        anchor = (e.start_ns, e.end_ns)
    if anchor is None:
        raise RuntimeError(f"no {ANCHOR!r} annotation in the trace")
    return Trace(ops, n, anchor)


def merge(intervals) -> list[tuple[float, float]]:
    """Union of (start, end) intervals, sorted and disjoint."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def gaps(busy, lo: float, hi: float) -> list[tuple[float, float]]:
    """The parts of [lo, hi] that ``busy`` (merged) leaves uncovered."""
    out, t = [], lo
    for s, e in clip(busy, lo, hi):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def overlap(a, b) -> float:
    """Total overlap of two merged interval lists."""
    i = j = 0
    acc = 0.0
    while i < len(a) and j < len(b):
        lo = max(a[i][0], b[j][0])
        hi = min(a[i][1], b[j][1])
        if hi > lo:
            acc += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return acc


def busy_share(tr: Trace, lo: float, hi: float) -> float:
    """Busy seconds over [lo, hi], averaged over the devices traced."""
    return total(clip(merge((s, e) for _, s, e in tr.ops), lo, hi)) / (
        1e9 * max(tr.n_devices, 1))


def kernel_rule(kernel: str) -> re.Pattern:
    with open(KERNELS) as f:
        return re.compile(json.load(f)["kernels"][kernel]["match"])


def kernel_events(tr: Trace, kernel: str) -> list[tuple[str, float, float]]:
    rule = kernel_rule(kernel)
    return [op for op in tr.ops if rule.search(op[0])]


def short(name: str) -> str:
    """An op's HLO instruction name, without its shapes and operands."""
    return name.split(" = ", 1)[0]


def top_ops(tr: Trace, lo: float, hi: float, n: int = 10
            ) -> list[list]:
    """[[name, seconds], ...] of the device ops with the most time in
    [lo, hi], summed by HLO instruction name."""
    by: dict[str, float] = {}
    for name, s, e in tr.ops:
        d = min(e, hi) - max(s, lo)
        if d > 0:
            by[short(name)] = by.get(short(name), 0.0) + d
    ranked = sorted(by.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in ranked]


def host_states(spans, offset: float) -> dict[str, list]:
    """Merged trace-clock intervals for what the serving host was doing,
    from the program's spans: ``encode_pack`` (a batch's runtime call open,
    before the event program's call), ``dispatch`` (the event program's
    call), ``readback`` (after that call, runtime still open),
    ``batch_bookkeeping`` (a batch open outside its runtime call)."""
    by_name: dict[str, list] = {}
    for s in spans:
        by_name.setdefault(s.name, []).append(s)
    fwd = {(s.trace, s.parent): s for s in by_name.get("accel.forward", [])}
    pre, call, post, runtime = [], [], [], []
    for r in by_name.get("runtime", []):
        rs, re_ = r.wall_ns_start + offset, r.wall_ns_end + offset
        runtime.append((rs, re_))
        f = fwd.get((r.trace, r.sid))
        if f is None:
            pre.append((rs, re_))
            continue
        fs, fe = f.wall_ns_start + offset, f.wall_ns_end + offset
        pre.append((rs, fs))
        call.append((fs, fe))
        post.append((fe, re_))
    batch = merge((s.wall_ns_start + offset, s.wall_ns_end + offset)
                  for s in by_name.get("batch", []))
    runtime = merge(runtime)
    outside = []
    for bs, be in batch:
        outside += gaps(runtime, bs, be)
    return {"encode_pack": merge(pre), "dispatch": merge(call),
            "readback": merge(post), "batch_bookkeeping": merge(outside),
            "_batch": batch}


def idle_by_host_state(tr: Trace, spans, offset: float, lo: float,
                       hi: float, longest: int = 5) -> list[list]:
    """[[name, idle seconds], ...]: first the device's idle time in
    [lo, hi] summed by what the host was doing then (``total:<state>``;
    ``no_batch_open`` is the lane waiting for requests or forming a batch),
    then the ``longest`` single idle gaps, each named by the state that
    covers most of it and its start in seconds into the window
    (``gap:<state>@<s>``)."""
    idle = gaps(merge((s, e) for _, s, e in tr.ops), lo, hi)
    states = host_states(spans, offset)
    batch = states.pop("_batch")
    states["no_batch_open"] = gaps(batch, lo, hi)
    totals = sorted(((k, overlap(idle, v)) for k, v in states.items()),
                    key=lambda kv: -kv[1])
    out = [[f"total:{k}", v / 1e9] for k, v in totals]
    for s, e in sorted(idle, key=lambda g: g[0] - g[1])[:longest]:
        state = max(states, key=lambda k: overlap([(s, e)], states[k]))
        out.append([f"gap:{state}@{(s - lo) / 1e9:.3f}", (e - s) / 1e9])
    return out
