"""Run every benchmark (one per paper table/figure) and print results.

    PYTHONPATH=src python -m benchmarks.run [--only table1_main,...]

The serving benchmarks need the cached artifacts (built automatically on
first use: `python -m benchmarks.common`).
"""
from __future__ import annotations

import argparse
import time
import traceback

from repro.launch.compile_cache import enable_compile_cache

BENCHES = [
    ("table1_main", "Table 1: acc/tokens/latency across methods"),
    ("table2_voting", "Table 2: voting strategies"),
    ("table3_breakdown", "Table 3: wait vs decode breakdown"),
    ("table4_memory", "Table 4: memory sensitivity"),
    ("fig4_scaling", "Fig 4: latency scaling with trace budget"),
    ("fig5_rankacc", "Fig 5: scorer vs confidence RankAcc"),
    ("overhead", "Appendix D: scorer overhead"),
]


def main() -> int:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated benchmark names")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None

    failures = 0
    for name, desc in BENCHES:
        if only and name not in only:
            continue
        print(f"\n=== {name}: {desc} ===", flush=True)
        t0 = time.time()
        try:
            mod = __import__(f"benchmarks.{name}", fromlist=["main"])
            mod.main()
            print(f"=== {name} done in {time.time() - t0:.1f}s ===",
                  flush=True)
        except Exception:
            failures += 1
            print(f"=== {name} FAILED ===")
            traceback.print_exc()
    print(f"\nbenchmarks: {'ALL OK' if not failures else f'{failures} FAILED'}")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
