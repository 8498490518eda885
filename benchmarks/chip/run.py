"""Chip benchmark: serve one cell of ``BENCHMARK.json`` on the TPU this
process finds, and print one JSON result line.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Exits non-zero, printing no result, unless JAX finds TPUs of a kind in
``peaks.json``, as many as the cell asks for. ``--trace 0`` reports the
cell's end-to-end metrics; ``--trace 1`` reports its per-layer metrics from
the program's spans and a device profile of the same window.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

_ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(_ROOT), str(_ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    from benchmarks.chip import harness
    out = harness.run(a.workload, a.seed, a.seconds, bool(a.trace), T_START)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
