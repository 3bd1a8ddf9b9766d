#!/usr/bin/env python3
"""Readings that set a cell's limits: the program's numbers over many
seeds, its control's, and each fault's, in one process (one compile).

  python3 benchmarks/chip/calibrate.py --workload whisper-tiny.train \\
      --seeds 11,12,13 --control --faults half_batch,answer_altered

For every seed it prepares the cell as a run does (set-up and first
steps or the warm-up round, no window), compares with the reference, and
prints one JSON line: the program's numbers, the control's (the reference
in the program's place, one precision below the configuration's), and
each fault's (the timed path broken underneath).  The benchmark's own
runs never do this.  Lines also go to ``chiprun_out/calibrate/``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--faults", default="",
                    help="comma-separated fault names of the cell's kind")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(HERE))
    sys.path.insert(0, str(HERE.parents[1] / "src"))
    import harness
    cell = harness.Cell.load(args.workload, args.rehearse)
    jax = harness.setup_jax(cache=not args.rehearse)
    try:
        devs = harness.devices(jax, cell.chips, args.rehearse)
    except harness.NoChip as e:
        print(f"calibrate.py: {e}", file=sys.stderr)
        return 2
    out_dir = harness.ROOT / "chiprun_out" / "calibrate"
    out_dir.mkdir(parents=True, exist_ok=True)
    runner = cell.kind.Runner(cell, jax, devs)
    t = time.perf_counter()
    runner.build()
    print(f"[calibrate] build {time.perf_counter() - t:.3f} s", flush=True)
    faults = [f for f in args.faults.split(",") if f]
    with open(out_dir / f"{args.workload}.jsonl", "a") as fh:
        for seed in (int(s) for s in args.seeds.split(",")):
            line = {"seed": seed, "kind": devs[0].device_kind}
            runner.variant = "program"
            t = time.perf_counter()
            runner.prepare(seed)
            line["prepare_s"] = time.perf_counter() - t
            runner.finish(keep_program=True)
            t = time.perf_counter()
            line["program"] = runner.check(seed)
            line["reference_s"] = time.perf_counter() - t
            if args.control:
                t = time.perf_counter()
                line["control"] = runner.control()
                line["control_s"] = time.perf_counter() - t
            for fault in faults:
                runner.variant = fault
                runner.prepare(seed)
                runner.finish(keep_program=True)
                line[fault] = runner.fault_numbers()
            runner.release()
            print(json.dumps(line), flush=True)
            fh.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
