"""Observability report driver: validate + digest obs.v1 snapshots.

Reads the snapshot ``launch/train.py --obs`` (or the sim engine's
``CampaignResult.obs``) wrote, schema-validates it, and prints a compact
digest: counters, gauges, histogram mass, the span-ring tail.  With
``--kernels`` it additionally runs the Pallas stats/apply kernels at a
small (n, d) grid under a :class:`repro.obs.KernelProfiler` and reports
each launch's chosen ``d_tile`` / grid depth next to the
``analysis/vmem.py`` prediction (and XLA's measured temp bytes where the
backend exposes them).

Usage:
  PYTHONPATH=src python -m repro.launch.obs_report \\
      --snapshot obs_snapshot.json [--trace obs_trace.json] \\
      [--validate] [--kernels]

``--validate`` exits 1 on any schema problem — CI runs it on the smoke
snapshot; ``--trace`` additionally checks the Chrome-trace file parses,
that its host spans have durations and its span-ring records none (they
are instants: the ring holds no time), and counts its events.
"""
from __future__ import annotations

import argparse
import json
from typing import Optional, Tuple

from repro import obs as OBS

#: (n, d) grid for --kernels: one shallow and one multi-step launch per
#: kernel, small enough for CPU interpret mode
KERNEL_POINTS = ((11, 4096), (15, 65536))


def _digest(snap) -> None:
    m = snap.get("metrics") or {}
    print(f"[obs_report] schema={snap.get('schema')} "
          f"meta={json.dumps(snap.get('meta', {}), sort_keys=True)}")
    for name, v in sorted((m.get("counters") or {}).items()):
        print(f"[obs_report] counter {name} = {v:g}")
    for name, v in sorted((m.get("gauges") or {}).items()):
        flat = v if isinstance(v, list) else [v]
        if len(flat) > 4:
            print(f"[obs_report] gauge {name} = "
                  f"[{flat[0]:.4g} .. {flat[-1]:.4g}] ({len(flat)} slots)")
        else:
            print(f"[obs_report] gauge {name} = "
                  f"{[round(float(x), 4) for x in flat]}")
    for name, h in sorted((m.get("hists") or {}).items()):
        total = sum(h["counts"])
        print(f"[obs_report] hist {name}: {total} obs over "
              f"{len(h['edges']) + 1} buckets, counts={h['counts']}")
    recs = (snap.get("trace") or {}).get("records", [])
    print(f"[obs_report] span ring: {len(recs)} records retained")
    for r in recs[-8:]:
        print(f"[obs_report]   seq={r['seq']:>5} round={r['round']:>5} "
              f"{r['phase']:<12} payload={r['payload']:.4g}")
    sv = snap.get("serve")
    if sv:
        print(f"[obs_report] serve: rounds={sv.get('rounds')} "
              f"round_us p50/p95/p99 = "
              f"{sv['round_us']['p50']:.0f}/{sv['round_us']['p95']:.0f}/"
              f"{sv['round_us']['p99']:.0f}")


def _trace_problems(events) -> list:
    """Host spans (pid 0) are complete events with a duration; ring
    records (pid 1) are instants, whose time is their step's end."""
    problems = []
    for e in events:
        ph, pid = e.get("ph"), e.get("pid")
        if ph == "M":
            continue
        if pid == 0 and not (ph == "X" and e.get("dur", -1) >= 0):
            problems.append(f"host event {e.get('name')!r} is not a span "
                            "with a duration")
        elif pid == 1 and (ph != "i" or "dur" in e):
            problems.append(f"ring record {e.get('name')!r} carries a "
                            "duration: the ring holds no time")
        elif pid not in (0, 1):
            problems.append(f"event {e.get('name')!r} on unknown pid {pid}")
    return problems


def _kernel_report(points: Tuple[Tuple[int, int], ...]) -> None:
    for rec in OBS.profile_points(points):
        pred = rec["vmem_predicted"]
        meas = rec["vmem_measured"]
        print(f"[obs_report] kernel {rec['kernel']:<15} "
              f"n={rec['n']:<4} d={rec['d']:<8} "
              f"d_tile={rec['d_tile']:<6} macro={rec['macro_tile']:<6} "
              f"grid={rec['grid_steps']:<3} "
              f"vmem_pred={'-' if pred is None else pred} "
              f"vmem_meas={'-' if meas is None else meas} "
              f"over_budget={rec['over_budget']}")


def main(argv: Optional[Tuple[str, ...]] = None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--snapshot", default="obs_snapshot.json",
                    help="obs.v1 snapshot to digest")
    ap.add_argument("--trace", default=None,
                    help="Chrome-trace JSON to check (optional)")
    ap.add_argument("--validate", action="store_true",
                    help="exit 1 on any schema problem")
    ap.add_argument("--kernels", action="store_true",
                    help="profile the Pallas kernel launch configs at a "
                         "small (n, d) grid (runs the real kernels)")
    args = ap.parse_args(argv)

    problems = []
    try:
        with open(args.snapshot) as fh:
            snap = json.load(fh)
    except FileNotFoundError:
        problems.append(f"{args.snapshot}: missing — run "
                        "`python -m repro.launch.train --obs` first")
        snap = None
    except json.JSONDecodeError as e:
        problems.append(f"{args.snapshot}: not valid JSON ({e})")
        snap = None
    if snap is not None:
        problems += [f"{args.snapshot}: {p}"
                     for p in OBS.validate_snapshot(snap)]
        _digest(snap)

    if args.trace:
        try:
            with open(args.trace) as fh:
                doc = json.load(fh)
            events = doc.get("traceEvents")
            if not isinstance(events, list) or not events:
                problems.append(f"{args.trace}: no traceEvents")
            else:
                problems += [f"{args.trace}: {p}"
                             for p in _trace_problems(events)]
                n_ring = sum(1 for e in events if e.get("ph") == "i")
                n_host = sum(1 for e in events if e.get("ph") == "X")
                print(f"[obs_report] trace: {len(events)} events "
                      f"({n_host} host spans, {n_ring} ring records) — "
                      "open at https://ui.perfetto.dev")
        except FileNotFoundError:
            problems.append(f"{args.trace}: missing")
        except json.JSONDecodeError as e:
            problems.append(f"{args.trace}: not valid JSON ({e})")

    if args.kernels:
        _kernel_report(KERNEL_POINTS)

    for p in problems:
        print(f"[obs_report] PROBLEM: {p}")
    if problems and args.validate:
        return 1
    if not problems:
        print("[obs_report] OK")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
