"""One benchmark run of one cell: resolve the cell's names to its files,
build the deployment from the seed, serve the cell's traffic for the window
through ``ServingScheduler``, check every answer against the plain
reference, and print the result line.

Everything a cell needs is found by name: ``BENCHMARK.json`` names the
cell's configuration (``configs/<file>``), traffic (``traffic/<name>.json``)
and metrics; each metric's reader is ``metrics/<name>.py``, or, for a
metric named ``<base>.<variant>``, ``metrics/<base>.py``. The configuration
names its deployment module (``"module"``, default ``model``; its interface
is in ``model.py``'s docstring), which builds, exports and checks the
deployment.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import shutil
import sys
import tempfile
import time
import types
from pathlib import Path

import numpy as np

from benchmarks.chip import data, devtrace, load, work

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
# Requests completed by the scheduler's warm-up before the window opens.
WARM_ROUNDS = 2
TRACER_SPANS = 1 << 21


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    cfg: dict
    traffic: dict
    metrics: list[dict]
    module: types.ModuleType | None = None   # None: the one cfg names

    def __post_init__(self):
        if self.module is None:
            self.module = importlib.import_module(
                f"benchmarks.chip.{self.cfg.get('module', 'model')}")


def load_cell(workload: str, trace: bool, root: Path = ROOT) -> Cell:
    with open(root / "BENCHMARK.json") as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    w = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(root / conf["file"]) as f:
        cfg = json.load(f)
    with open(HERE / "traffic" / f"{w['traffic']}.json") as f:
        traffic = json.load(f)
    group = bench["per_layer"] if trace else bench["end_to_end"]
    metrics = [m for m in group
               if workload in m.get("workloads", [workload])]
    return Cell(workload, int(w["chips"]), cfg, traffic, metrics)


def reader(name: str):
    """The ``read(run)`` function of metric ``name``."""
    path = HERE / "metrics" / f"{name}.py"
    if not path.exists():
        path = HERE / "metrics" / f"{name.split('.')[0]}.py"
    spec = importlib.util.spec_from_file_location(
        f"benchmarks.chip.metrics.{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def find_chips(chips: int):
    """The chips to run on and their peaks; exits unless JAX's devices are
    TPUs in the peaks table, as many as the cell asks for."""
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SystemExit(f"chip benchmark: needs a TPU, JAX found platform "
                         f"{d.platform!r}")
    try:
        peak = work.peak_for(d.device_kind)
    except KeyError as e:
        raise SystemExit(f"chip benchmark: {e}") from None
    if len(devs) < chips:
        raise SystemExit(f"chip benchmark: the cell asks for {chips} chips, "
                         f"JAX found {len(devs)}")
    return devs[:chips], peak


@dataclasses.dataclass
class Run:
    """What a metric reader reads."""
    cell: Cell
    seconds: float
    t0: float                 # window start, perf_counter seconds
    setup_s: float
    records: load.Records
    correct_rows: np.ndarray  # (len(records),) answer matches the reference
    events: np.ndarray        # (pool, L) events each pool image feeds a layer
    widths: list              # [(n_in, n_out)] of each layer, real neurons
    stats: dict               # ServingScheduler.stats() after the window
    peak: dict                # the chip's peaks (work.load_peaks)
    spans: list | None = None         # program spans, traced run only
    spans_dropped: int = 0
    trace: devtrace.Trace | None = None
    offset_ns: float = 0.0    # perf_counter_ns + offset = trace clock

    @property
    def t1(self) -> float:
        return self.t0 + self.seconds

    def in_window(self) -> np.ndarray:
        """Rows completed inside the window."""
        d = self.records.done
        return (d >= self.t0) & (d <= self.t1)

    def due_in_window(self) -> np.ndarray:
        return (self.records.due >= self.t0) & (self.records.due <= self.t1)

    def window_ns(self) -> tuple[float, float]:
        """The window on the trace's clock."""
        return (self.t0 * 1e9 + self.offset_ns,
                self.t1 * 1e9 + self.offset_ns)


def _warm(sched, pool: np.ndarray) -> None:
    """Serve every shape and path the window can take: full batches of pool
    images, and one image whose events overflow E_max in a step, which
    takes the dense reroute."""
    overflow = np.ones(pool.shape[1], np.float32)
    for _ in range(WARM_ROUNDS):
        rids = [sched.submit(img) for img in pool[:sched.max_batch]]
        rids.append(sched.submit(overflow))
        for rid in rids:
            sched.result(rid, timeout=600)


def _start_profiler():
    """Device and runtime tracing on, Python function tracing off: the
    latter records every call of the serving host's Python and slows it
    several times over."""
    import jax
    logdir = tempfile.mkdtemp(prefix="chipbench-trace-")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(logdir, profiler_options=options)
    return logdir


def _gc_pauses():
    """[(generation, seconds)] of every garbage collection from now on."""
    pauses, start = [], [0.0]

    def callback(phase: str, info: dict) -> None:
        if phase == "start":
            start[0] = time.perf_counter()
        else:
            pauses.append((info["generation"], time.perf_counter() - start[0]))

    gc.callbacks.append(callback)
    return pauses


def _compile_counter():
    """A count of XLA compilations, bumped by JAX's monitoring events."""
    import jax
    count = [0]

    def listener(event: str, *args, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            count[0] += 1

    jax.monitoring.register_event_duration_secs_listener(listener)
    return count


def inputs(cell: Cell, seed: int):
    """(deployment, pool images, pool order, arrival seed) from the seed."""
    seed_model, seed_pool, seed_order, seed_arrivals = data.sub_seeds(seed, 4)
    dep = cell.module.build(cell.cfg, seed_model)
    pool, _ = data.generate(cell.traffic["pool_images"], seed_pool)
    order = np.random.RandomState(seed_order).permutation(len(pool))
    return dep, pool, order, seed_arrivals


def build(cell: Cell, seed: int):
    """Everything a run sets up from the seed, up to a warm scheduler:
    (deployment, pool images, pool order, arrival seed, scheduler)."""
    import jax
    from repro.compile_cache import enable_compile_cache
    from repro.serving.scheduler import ServingScheduler
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    serve = cell.traffic["serve"]
    dep, pool, order, seed_arrivals = inputs(cell, seed)
    sched = ServingScheduler(
        cell.module.artifact(dep), spec=serve["spec"], kernel=serve["kernel"],
        workers=serve["workers"], max_batch=serve["max_batch"],
        max_wait_us=serve["max_wait_us"],
        latency_mode=bool(serve["latency_mode"]))
    try:
        _warm(sched, pool)
    except BaseException:
        sched.close()
        raise
    return dep, pool, order, seed_arrivals, sched


def drive(sched, cell: Cell, pool, order, seed_arrivals: int,
          seconds: float) -> tuple[load.Records, float]:
    """Offer the cell's load for ``seconds``; returns (records, start)."""
    spec = cell.traffic["load"]
    if spec["kind"] == "closed":
        return load.closed_loop(sched, pool, order, spec["outstanding"],
                                seconds)
    if spec["kind"] == "poisson":
        schedule = data.poisson_schedule(spec["rate_per_s"], seconds,
                                         seed_arrivals)
        return load.open_loop(sched, pool, order, schedule)
    raise ValueError(f"unknown load kind {spec['kind']!r}")


def run(workload: str, seed: int, seconds: float, trace: bool,
        t_start: float) -> dict:
    """One run; returns the result line's object."""
    cell = load_cell(workload, trace)
    devs, peak = find_chips(cell.chips)
    import jax
    from repro.core.lowering import get_cache
    from repro.telemetry import trace as ttrace
    compiles = _compile_counter()
    latency = bool(cell.traffic["serve"]["latency_mode"])
    dep, pool, order, seed_arrivals, sched = build(cell, seed)
    try:
        sched.reset_stats()
        tracer = logdir = None
        if trace:
            tracer = ttrace.Tracer(max_spans=TRACER_SPANS)
            ttrace.install(tracer)
            logdir = _start_profiler()
        # Set-up's objects (JAX, the program, the pool) go to the permanent
        # generation: a full collection in the window then walks only what
        # serving allocates, not the 1e5 objects of a loaded JAX process.
        gc.collect()
        gc.freeze()
        pauses = _gc_pauses()
        compiled_before = compiles[0]
        setup_s = time.perf_counter() - t_start
        anchor_ns = time.perf_counter_ns()
        with jax.profiler.TraceAnnotation(devtrace.ANCHOR):
            records, t0 = drive(sched, cell, pool, order, seed_arrivals,
                                seconds)
            time.sleep(max(0.0, t0 + seconds - time.perf_counter()))
        in_window = {"compiles": compiles[0] - compiled_before,
                     "gc": list(pauses)}
        if trace:
            jax.profiler.stop_trace()
            ttrace.install(None)
        stats = sched.stats()
    finally:
        sched.close()
    memory_peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                      for d in devs)
    del sched
    get_cache().clear()

    mod = cell.module
    want_label, want_steps = mod.answers(dep, pool, latency)
    img = records.image
    ok = (~records.error & (records.label == want_label[img])
          & (records.steps == want_steps[img]))
    result = Run(cell, seconds, t0, setup_s, records, ok,
                 mod.events(dep, pool), mod.widths(cell.cfg), stats, peak)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": memory_peak}
    breakdown = None
    if trace:
        result.spans = tracer.sorted_spans()
        result.spans_dropped = tracer.dropped
        result.trace = devtrace.load_xplane(logdir)
        shutil.rmtree(logdir, ignore_errors=True)
        result.offset_ns = result.trace.anchor[0] - anchor_ns
        lo, hi = result.window_ns()
        device["busy_s"] = devtrace.busy_share(result.trace, lo, hi)
        device["window_s"] = (hi - lo) / 1e9
        breakdown = {"device_ops": devtrace.top_ops(result.trace, lo, hi),
                     "idle_gaps": devtrace.idle_by_host_state(
                         result.trace, result.spans, result.offset_ns,
                         lo, hi)}

    metrics = {}
    for m in cell.metrics:
        value = reader(m["name"])(result)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    served = ~records.error
    checks = {
        "wrong_labels": (int(np.sum(served & (records.label
                                               != want_label[img]))), 0),
        "wrong_steps": (int(np.sum(served & (records.steps
                                              != want_steps[img]))), 0),
        "unanswered": (int(records.error.sum()), 0),
    }
    _report(result, dep, in_window, checks)
    out = {"correct": all(v <= lim for v, lim in checks.values()),
           "attempted": int(len(records)), "failed": int(np.sum(~ok)),
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in checks.items()}
    return out


def _report(run: Run, dep, in_window: dict, checks: dict) -> None:
    """What the run saw, on stderr; the compared numbers come last."""
    rec = run.records
    full = [d for g, d in in_window["gc"] if g == 2]
    lat = rec.done - rec.due
    worst = np.argsort(np.nan_to_num(lat, nan=np.inf))[-3:][::-1]
    print(f"garbage collections in the window: {len(in_window['gc'])}, of "
          f"them {len(full)} full, {1e3 * sum(full):.1f} ms; slowest "
          "requests " + ", ".join(f"{1e3 * lat[k]:.1f} ms due at "
                                  f"{rec.due[k] - run.t0:.3f} s"
                                  for k in worst), file=sys.stderr)
    print(f"compiles inside the window: {in_window['compiles']}; requests "
          f"{len(rec)}; served by the dense reroute: "
          f"{run.stats['overflow_fallbacks']}; training accuracy of the "
          f"deployment {dep.train_accuracy:.4f}", file=sys.stderr)
    if run.spans_dropped:
        print(f"program spans dropped: {run.spans_dropped}", file=sys.stderr)
    for k, (v, lim) in checks.items():
        print(f"check {k}: {v} (limit {lim})", file=sys.stderr)
