"""The lane's spans in the batches served in the window, for the per-layer
readers. A batch is one trace (``batch-<seq>``) whose ``batch`` span opened
inside the window. Each reader returns ``None`` when the run was untraced,
spans were dropped, or the program records no span it reads."""

# The lane waiting for a first request, then forming the batch.
WAIT = ("lane.idle", "batch.form")
# The batch's phases, one after another on the lane's thread.
PHASES = ("lane.pad", "lane.encode", "lane.pack", "accel.dispatch",
          "lane.device_wait", "lane.readback", "lane.reroute",
          "batch.complete")
LANE = WAIT + PHASES
# Spans over this long are stalls.
STALL_NS = 50e6


def wall_ns(span) -> float:
    return span.wall_ns_end - span.wall_ns_start


def window_batches(run) -> dict | None:
    """{trace: [spans]} of the batches that opened in the window."""
    if run.spans is None or run.spans_dropped:
        return None
    lo, hi = run.t0 * 1e9, run.t1 * 1e9
    out = {s.trace: [] for s in run.spans
           if s.name == "batch" and lo <= s.wall_ns_start <= hi}
    for s in run.spans:
        if s.trace in out:
            out[s.trace].append(s)
    return out or None


def _inside_a_phase(span, by_sid: dict) -> bool:
    """Whether one of the span's ancestors is a phase: the dense reroute's
    own forward and dispatch are part of ``lane.reroute``."""
    parent = by_sid.get(span.parent)
    while parent is not None:
        if parent.name in PHASES:
            return True
        parent = by_sid.get(parent.parent)
    return False


def named(run, names) -> tuple[int, list] | None:
    """(batches in the window, their spans named ``names`` that no phase
    contains), or None when there is no such span."""
    batches = window_batches(run)
    if batches is None:
        return None
    found = []
    for spans in batches.values():
        by_sid = {s.sid: s for s in spans}
        found += [s for s in spans
                  if s.name in names and not _inside_a_phase(s, by_sid)]
    return (len(batches), found) if found else None


def ms_per_batch(run, *names) -> float | None:
    """Summed wall time of the spans named ``names`` over the window's
    batches, per batch, in ms."""
    found = named(run, names)
    if found is None:
        return None
    n, spans = found
    return sum(wall_ns(s) for s in spans) / n / 1e6
