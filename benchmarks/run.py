"""Benchmark harness — one section per paper table/figure + roofline.

Prints ``name,us_per_call,derived`` CSV rows (value column semantics noted
per section).  Sections:

* agg_time    — Fig 2: aggregation wall-time vs (n, d), O(d)/O(n²) scaling,
                XLA vs Pallas vs fused apply substrates; persists the perf
                trajectory to BENCH_agg_time.json
* accuracy    — Fig 3: max top-1 accuracy per GAR × per-worker batch size;
                persists BENCH_accuracy.json
* resilience  — rule × attack campaign sweep through the sim engine
                (post-switch honest-mean deviation, byzantine selection
                mass); persists BENCH_resilience.json
* bandwidth   — wire bytes/step + round time per codec × (n, d) through
                repro.comm; persists BENCH_comm.json
* hier        — hierarchical vs flat aggregation at large n (repro.hier):
                O(n·g) grouped selection where the flat O(n²) path is
                infeasible; persists BENCH_hier.json
* serving     — closed-loop async vs sync robust serving throughput
                (repro.serve): QPS × staleness bound × f with the stale
                accounting replayed through the real gradient buffer;
                persists BENCH_serving.json
* roofline    — §Roofline terms from the dry-run artifacts (if present)

Env: BENCH_SECTIONS=agg_time,accuracy,... to select a subset (unknown
section names are an error — a typo must not silently skip a section).
``--smoke`` shrinks every section to a CI-sized grid (all four JSONs are
still written so the trajectory checks have something to validate).
A section that cannot run (roofline without the dry-run artifact) prints
an explicit skip reason; ``--strict`` turns any such skip into a non-zero
exit.
"""
from __future__ import annotations

import argparse
import os
import sys
import time
from typing import List

KNOWN_SECTIONS = ("agg_time", "accuracy", "resilience", "bandwidth",
                  "hier", "serving", "roofline")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized grids for every selected section")
    ap.add_argument("--strict", action="store_true",
                    help="fail (exit 1) when any selected section skips "
                         "instead of running")
    ap.add_argument("--bench-json", default=None,
                    help="agg_time JSON output path (default "
                         "BENCH_agg_time.json in the cwd)")
    ap.add_argument("--resilience-json", default="BENCH_resilience.json",
                    help="resilience sweep JSON output path")
    ap.add_argument("--comm-json", default="BENCH_comm.json",
                    help="bandwidth sweep JSON output path")
    ap.add_argument("--accuracy-json", default="BENCH_accuracy.json",
                    help="accuracy JSON output path")
    ap.add_argument("--hier-json", default="BENCH_hier.json",
                    help="hierarchical scaling JSON output path")
    ap.add_argument("--serving-json", default="BENCH_serving.json",
                    help="closed-loop serving JSON output path")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    default_sections = "agg_time,accuracy,resilience,bandwidth,hier,serving" \
        if args.smoke else \
        "agg_time,accuracy,resilience,bandwidth,hier,serving,roofline"
    sections = os.environ.get("BENCH_SECTIONS", default_sections).split(",")
    unknown = [s for s in sections if s not in KNOWN_SECTIONS]
    if unknown:
        print(f"unknown BENCH_SECTIONS entries {unknown}; "
              f"known: {list(KNOWN_SECTIONS)}", file=sys.stderr)
        sys.exit(2)
    rows: List[str] = []
    skipped: List[str] = []
    t0 = time.time()
    if "agg_time" in sections:
        from benchmarks import agg_time
        kw = {} if args.bench_json is None else {"json_path": args.bench_json}
        agg_time.run(rows, smoke=args.smoke, **kw)
        print(f"# agg_time done ({time.time()-t0:.0f}s)", file=sys.stderr)
    if "accuracy" in sections:
        from benchmarks import accuracy
        accuracy.run(rows, smoke=args.smoke, json_path=args.accuracy_json)
        print(f"# accuracy done ({time.time()-t0:.0f}s)", file=sys.stderr)
    if "resilience" in sections:
        from benchmarks import resilience
        resilience.run(rows, smoke=args.smoke,
                       json_path=args.resilience_json)
        print(f"# resilience done ({time.time()-t0:.0f}s)", file=sys.stderr)
    if "bandwidth" in sections:
        from benchmarks import bandwidth
        bandwidth.run(rows, smoke=args.smoke, json_path=args.comm_json)
        print(f"# bandwidth done ({time.time()-t0:.0f}s)", file=sys.stderr)
    if "hier" in sections:
        from benchmarks import hier_scale
        hier_scale.run(rows, smoke=args.smoke, json_path=args.hier_json)
        print(f"# hier done ({time.time()-t0:.0f}s)", file=sys.stderr)
    if "serving" in sections:
        from benchmarks import serving
        serving.run(rows, smoke=args.smoke, json_path=args.serving_json)
        print(f"# serving done ({time.time()-t0:.0f}s)", file=sys.stderr)
    if "roofline" in sections:
        from benchmarks import roofline
        derived = roofline.run(rows)
        if not derived:
            reason = ("roofline: SKIPPED — results/dryrun_single_pod.json "
                      "absent; generate it with `python -m "
                      "repro.launch.dryrun --all --json` first")
            print(f"# {reason}", file=sys.stderr)
            skipped.append(reason)
        else:
            print(f"# roofline done ({time.time()-t0:.0f}s)",
                  file=sys.stderr)
    print("name,us_per_call,derived")
    print("\n".join(rows))
    if skipped and args.strict:
        print(f"--strict: {len(skipped)} section(s) skipped:",
              file=sys.stderr)
        for reason in skipped:
            print(f"  {reason}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
