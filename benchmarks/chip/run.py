#!/usr/bin/env python3
"""The chip benchmark: one cell of ``BENCHMARK.json``, one run.

  python3 benchmarks/chip/run.py --workload whisper-tiny.train \\
      --seed 1234 --seconds 30 --trace 0

Set-up (imports, compiles or cache loads, weights and inputs made on the
device from ``--seed``, the warm-up) is timed as ``setup_s``; then the
cell's steps or rounds run for ``--seconds``, each waited for; then the
program's state is freed and what the window produced is compared with
the plain reference.  The last line of standard output is the result
(JSON); the last lines of standard error are the numbers compared, each
with its limit.  With ``--trace 1`` the window is traced and the result
carries the per-layer metrics instead of the end-to-end ones.

Without a TPU, or with fewer chips than the cell asks for, it exits 2
and prints no result.  ``--rehearse`` runs the cell on the CPU at the
family's rehearsal sizes with the kernels interpreted, to check control
flow only, and prints no result line.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at reduced sizes; no result line")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    sys.path.insert(0, str(Path(__file__).resolve().parent))
    import harness
    try:
        result = harness.run(args.workload, args.seed, args.seconds,
                             bool(args.trace), rehearse=args.rehearse,
                             t0=T0)
    except harness.NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    if args.rehearse:
        print(f"run.py: rehearsal finished, correct={result['correct']} "
              f"(CPU, control flow only, not a chip run)")
        return 0
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
