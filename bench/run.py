#!/usr/bin/env python3
"""Run one cell of the benchmark and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``. The last line
of standard output is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``, and last ``check``: every number compared beside its
limit). Without a TPU, with fewer chips than the cell needs, with any
``REPRO_*`` variable set, or outside a checkout of the repository, the
run exits non-zero and prints no result.

``--control 1`` also judges the correctness check's control (the plain
reference computed in fp8, below the stated bf16, in the engine's place)
on the same served tokens and limits, and prints its verdict and numbers
on standard error; the benchmark's own runs leave it off.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        from bench import harness
    except ImportError as e:
        print(f"bench: cannot import the harness or the program: {e}",
              file=sys.stderr)
        return 2
    try:
        out = harness.run(args.workload, args.seed, args.seconds,
                          bool(args.trace), t_process=T_PROCESS,
                          control=bool(args.control))
    except harness.Refused as e:
        print(f"bench: refused: {e}", file=sys.stderr)
        return 2
    except Exception:
        traceback.print_exc()
        return 1
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
