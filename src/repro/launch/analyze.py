import os
import sys

if "jax" not in sys.modules:                       # keep test imports inert
    os.environ.setdefault(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=8")

"""Static contract verification CLI (DESIGN.md §12).

Runs the three ``repro.analysis`` passes — the AST lint (R001–R005), the
jaxpr contract auditors (C201–C205) under a forced 8-device host mesh,
and the Pallas VMEM estimator — and writes the ``analysis.v1``
report.  No accelerator is required and no training step executes: the
auditors only *trace*.

Usage:
  PYTHONPATH=src python -m repro.launch.analyze [--json ANALYSIS.json]
  PYTHONPATH=src python -m repro.launch.analyze --strict   # CI gate

``--strict`` exits nonzero on any lint violation, any violated contract
or a failed traffic-linearity diagnosis — the gate every kernel/sharding
PR must pass.
"""
import argparse
import json
from typing import Dict, List

SCHEMA = "analysis.v1"

#: the committed BENCH grid points the kernel estimates are emitted at
KERNEL_POINTS = ((11, 4096), (15, 100_000), (15, 1_000_000))

LINT_PATHS = ("src", "benchmarks", "examples")


def run_lint(root: str = ".") -> Dict:
    from repro.analysis import lint
    paths = [os.path.join(root, p) for p in LINT_PATHS
             if os.path.isdir(os.path.join(root, p))]
    violations = lint.lint_paths(paths)
    return {
        "paths": [os.path.relpath(p, root) for p in paths],
        "rules": sorted(lint.RULES),
        "violations": [v.to_json() for v in violations],
    }


def run_contracts() -> Dict:
    import jax

    from repro.analysis import jaxpr_audit as JA
    from repro.core import api
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((4, 2), ("data", "model"))
    ctx = api.MeshContext.for_mesh(mesh)
    key = jax.random.key(0)
    grads = {"w": jax.random.normal(key, (11, 8, 32)),
             "b": jax.random.normal(jax.random.key(1), (11, 16))}

    results = [
        JA.audit_apply_gather(grads, f=2, mesh_ctx=ctx),
        JA.audit_decode_invariant(grads, f=2, mesh_ctx=ctx),
        JA.audit_tp_seam(
            jax.make_jaxpr(lambda g: api.aggregate_tree(
                g, 2, "multi_bulyan", mesh_ctx=ctx))(grads),
            label="aggregate_tree mesh path"),
        JA.tp_seam_self_test(),
        JA.audit_single_compile(
            jax.jit(lambda g: api.aggregate_tree(g, 2, "multi_bulyan")),
            lambda: (grads,), label="jitted aggregate_tree"),
        JA.audit_hier_decode(
            {"w": jax.random.normal(key, (21, 8, 32))}, f=1, spec="g=7"),
    ]
    return {r.contract: r.to_json() for r in results}


def run_kernels(bench_path: str) -> Dict:
    from repro.analysis import vmem

    kernels: Dict[str, Dict] = {}
    for kernel in ("fused_select", "pairwise_stats", "dequant_stats"):
        kernels[kernel] = {
            f"n={n},d={d}": vmem.estimate(kernel, n, d).to_json()
            for n, d in KERNEL_POINTS}
    out = {"kernels": kernels}
    if os.path.isfile(bench_path):
        with open(bench_path) as fh:
            bench = json.load(fh)
        out["traffic_linearity"] = vmem.diagnose_traffic_linearity(
            bench.get("results", bench))
    else:
        out["traffic_linearity"] = {"points": [], "holds": False,
                                    "detail": f"{bench_path} not found"}
    return out


def gate_problems(report: Dict) -> List[str]:
    """Everything ``--strict`` refuses to ship."""
    problems = []
    report = report["results"]
    for v in report["lint"]["violations"]:
        problems.append(
            f"lint {v['rule']} {v['path']}:{v['line']}: {v['msg']}")
    for name, res in report["contracts"].items():
        if res["status"] != "proven":
            problems.append(f"contract {name} violated: "
                            + "; ".join(res["violations"]))
    traffic = report["analysis"]["traffic_linearity"]
    if not traffic.get("holds"):
        problems.append("vmem traffic-linearity diagnosis does not hold: "
                        f"{traffic.get('detail')}")
    d1e6 = report["analysis"]["kernels"]["fused_select"].get("n=15,d=1000000")
    if d1e6 and not (d1e6["over_budget"] and not d1e6["tile_over_budget"]
                     and d1e6["macro_tile"] > d1e6["d_tile"]):
        problems.append("fused_select n=15,d=1e6 must tile (over_budget), "
                        "fit per macro step, and run a multi-window macro "
                        "block — the two-level residency claim fails")
    return problems


def build_report(root: str, bench_path: str) -> Dict:
    # the {"schema", "results"} envelope is what validate_bench gates on
    return {"schema": SCHEMA,
            "results": {"lint": run_lint(root),
                        "contracts": run_contracts(),
                        "analysis": run_kernels(bench_path)}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="static contract verification (lint + jaxpr audits "
                    "+ VMEM estimates)")
    ap.add_argument("--root", default=".",
                    help="repo root to lint (default: cwd)")
    ap.add_argument("--bench", default="BENCH_agg_time.json",
                    help="benchmark file for the cliff diagnosis")
    ap.add_argument("--json", default="ANALYSIS.json",
                    help="report output path ('-' for stdout only)")
    ap.add_argument("--strict", action="store_true",
                    help="exit nonzero on any violation")
    args = ap.parse_args(argv)

    report = build_report(args.root, args.bench)
    problems = gate_problems(report)

    if args.json != "-":
        with open(args.json, "w") as fh:
            json.dump(report, fh, indent=1, sort_keys=True)
            fh.write("\n")

    res_ = report["results"]
    nlint = len(res_["lint"]["violations"])
    print(f"lint: {nlint} violation(s) over {res_['lint']['paths']}")
    for name, res in sorted(res_["contracts"].items()):
        print(f"{name}: {res['status']} — {res['detail']}")
    traffic = res_["analysis"]["traffic_linearity"]
    print(f"vmem traffic linearity: holds={traffic.get('holds')}")
    if problems:
        print(f"\n{len(problems)} problem(s):")
        for p in problems:
            print(f"  ✗ {p}")
    else:
        print("\nall contracts proven, repo lints clean")
    if args.json != "-":
        print(f"report written to {args.json}")
    return 1 if (args.strict and problems) else 0


if __name__ == "__main__":
    sys.exit(main())
