"""Find the highest Poisson rate an open-loop cell sustains, by a sweep on
the chip: one process, one deployment, one warm scheduler, each rate offered
for ``--seconds``. A rate is sustained while the last tenth of its requests
waits no longer than the first tenth (no growing backlog).

    python3 benchmarks/chip/knee.py --workload <open-loop cell> --seed <n> \
        --seconds 8 --rates 2000 3000 4000

The result fixes the rate written into the cell's traffic file; the
benchmark's own runs never search for a rate.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

_ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(_ROOT), str(_ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    a = ap.parse_args(argv)
    import numpy as np
    from benchmarks.chip import harness
    cell = harness.load_cell(a.workload, trace=False)
    harness.find_chips(cell.chips)
    dep, pool, order, seed_arrivals, sched = harness.build(cell, a.seed)
    try:
        for rate in a.rates:
            traffic = dict(cell.traffic, load={"kind": "poisson",
                                               "rate_per_s": rate})
            c = dataclasses.replace(cell, traffic=traffic)
            sched.reset_stats()
            rec, t0 = harness.drive(sched, c, pool, order, seed_arrivals,
                                    a.seconds)
            lat = 1e3 * (rec.done - rec.due)
            tenth = max(1, len(lat) // 10)
            st = sched.stats()
            print(json.dumps({
                "rate_per_s": rate, "requests": len(rec),
                "errors": int(rec.error.sum()),
                "completed_per_s": float(np.sum(rec.done <= t0 + a.seconds))
                / a.seconds,
                "p50_ms": float(np.nanpercentile(lat, 50)),
                "p99_ms": float(np.nanpercentile(lat, 99)),
                "first_tenth_p50_ms": float(np.nanmedian(lat[:tenth])),
                "last_tenth_p50_ms": float(np.nanmedian(lat[-tenth:])),
                "gen_lag_p99_ms": float(np.nanpercentile(
                    1e3 * (rec.submit - rec.due), 99)),
                "batch_fill": st["batch_fill_mean"],
                "device_call_ms": 1e3 * st["accelerator_s"]
                / max(st["batches"], 1),
                "host_ms": 1e3 * st["host_overhead_s"]
                / max(st["batches"], 1)}), flush=True)
    finally:
        sched.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
