"""The control of the ``correct`` decision: the plain reference put in the
program's place one precision step below the configuration's (for the
``model`` module, int4 weights against the int8 stated), as the cell's
deployment module gives it, read by the same checks at a cell's own size.
Every reading has to fail a limit of 0, or the checks could not tell the
program from it.

    python3 benchmarks/chip/control.py --workload <cell> --requests <n> \
        --seeds 1 2 3

``--requests`` is how many requests a run of the cell serves; they cycle
through the pool in the run's own order. Prints one JSON line per seed.
The benchmark's own runs never run this.
"""

import argparse
import json
import sys
from pathlib import Path

_ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(_ROOT), str(_ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--requests", type=int, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    a = ap.parse_args(argv)
    import numpy as np
    from benchmarks.chip import harness
    cell = harness.load_cell(a.workload, trace=False)
    harness.find_chips(cell.chips)
    latency = bool(cell.traffic["serve"]["latency_mode"])
    for seed in a.seeds:
        dep, pool, order, _ = harness.inputs(cell, seed)
        img = order[np.arange(a.requests) % len(order)]
        want = cell.module.answers(dep, pool, latency)
        got = cell.module.answers(dep, pool, latency, control=True)
        print(json.dumps({
            "workload": a.workload, "seed": seed, "requests": a.requests,
            "wrong_labels": int(np.sum(got[0][img] != want[0][img])),
            "wrong_steps": int(np.sum(got[1][img] != want[1][img])),
            "limit": 0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
