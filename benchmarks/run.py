"""Benchmark harness entry point — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick]

| paper artifact        | module                  |
|-----------------------|-------------------------|
| Table 1 (resources)   | bench_resources         |
| Table 3 (cross-plat)  | bench_crossplatform     |
| Fig 2 (system path)   | bench_system_breakdown  |
| Fig 3 (sparsity)      | bench_sparsity          |
| §3.3 (repeatability)  | bench_repeatability     |
| Table 3 (board model) | bench_board_emu         |
| roofline (LM zoo)     | bench_roofline (reads results/dryrun) |

Every module that writes results/bench/ JSON does so through
``benchmarks.common.emit``, which validates rows against
``benchmarks.schema`` so the files stay comparable across PRs (scope +
identity + unit-suffixed metric fields). ``bench_roofline`` only prints
(it reads results/dryrun) and emits nothing.

JSON results land in results/bench/.
"""

from __future__ import annotations

import argparse
import sys
import time


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller test-set slices (CI mode)")
    ap.add_argument("--only", default=None,
                    help="run a single bench (e.g. sparsity)")
    args = ap.parse_args(argv)

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (bench_board_emu, bench_conformance,
                            bench_crossplatform, bench_event_pipeline,
                            bench_repeatability, bench_resources,
                            bench_roofline, bench_sparsity,
                            bench_system_breakdown)
    suite = [
        ("resources (Table 1)", bench_resources.main),
        ("crossplatform (Table 3)", bench_crossplatform.main),
        ("board_emu (Table 3 board model)", bench_board_emu.main),
        ("system_breakdown (Fig 2)", bench_system_breakdown.main),
        ("sparsity (Fig 3)", bench_sparsity.main),
        ("repeatability (sec 3.3)", bench_repeatability.main),
        ("event_pipeline (staged vs fused)", bench_event_pipeline.main),
        ("conformance (fuzzed cross-runtime agreement)",
         bench_conformance.main),
        ("roofline (LM zoo)", bench_roofline.main),
    ]
    for name, fn in suite:
        if args.only and args.only not in name:
            continue
        print(f"\n=== {name} " + "=" * max(0, 60 - len(name)))
        t0 = time.time()
        try:
            if fn is bench_roofline.main:
                fn()
            else:
                fn(quick=args.quick)
        except FileNotFoundError as e:
            print(f"[skipped: {e}]")
        print(f"[{name}: {time.time() - t0:.1f}s]")


if __name__ == "__main__":
    main()
