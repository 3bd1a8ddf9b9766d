"""Validate benchmark trajectory JSON files (CI gate).

Usage: python -m benchmarks.validate_bench [FILE ...]

Defaults to ``BENCH_agg_time.json``.  Four schemas are known, dispatched on
the payload's ``schema`` field:

* agg_time (``rule -> 'n=<n>,d=<d>' -> us_per_call``) — must contain the
  four apply substrate rows (multi_bulyan[xla|pallas|fused|sharded]) the
  perf trajectory exists to track, each at the full n ∈ {11, 15} ×
  d ∈ {4096, 1e5, 1e6} substrate grid; the fused row must be *monotone*:
  us_per_call/d non-increasing along d past 1e5 for every n (no deep-grid
  cliff) and within 1.1× the XLA row at the deepest point (n=15, d=1e6);
* resilience (``sim.resilience.v1``) — rule × attack campaign cells from
  ``benchmarks/resilience.py``, each with finite honest-mean deviation,
  byzantine selection mass in [0, 1] and a finite final loss;
* comm (``comm.v1``) — codec × (n, d) wire cells from
  ``benchmarks/bandwidth.py``: positive byte counts and round times, and
  the acceptance ordering wire_bytes fp32 > bf16 > qsgd int8 *strict* on
  every (n, d) point the three rows share;
* accuracy (``accuracy.v1``) — rule × per-worker-batch cells from
  ``benchmarks/accuracy.py``, accuracies in [0, 1];
* hier (``hier.v1``) — hierarchical vs flat scaling cells from
  ``benchmarks/hier_scale.py``: wherever n ≥ 1024 the flat path must be
  skipped-as-infeasible or ≥ 5× slower than the grouped path, and the
  grouped column must grow subquadratically in n (the O(n·g) vs O(n²)
  ordering gate);
* serving (``serving.v2``) — closed-loop async vs sync robust serving
  cells from ``benchmarks/serving.py``: both mode rows present with
  positive finite qps/round_us, per-cell p50/p95/p99 round latency in
  non-decreasing order, and async QPS *strictly above* sync on every
  shared (τ ≥ 1, f > 0) cell — the bounded-staleness buffer must
  actually buy throughput where the byzantine contract is live;
* analysis (``analysis.v1``) — the static-contract report from
  ``repro.launch.analyze``: zero committed lint violations, every
  sharding contract proven, two-level kernel estimates present at the
  committed grid points, the d=1e6 fused_select launch tiling under a
  budget-fitting multi-window macro block, and the traffic-linearity
  diagnosis holding (the deep-grid cliff stays closed).

Fails (exit 1) when a file is missing, is not JSON, or deviates from its
schema.
"""
from __future__ import annotations

import json
import math
import re
import sys

REQUIRED_ROWS = ("multi_bulyan[xla]", "multi_bulyan[pallas]",
                 "multi_bulyan[fused]", "multi_bulyan[sharded]")
#: the substrate (n, d) grid every REQUIRED_ROWS row must cover
#: (benchmarks/agg_time.py PATH_NS × PATH_DS)
REQUIRED_CELLS = tuple(f"n={n},d={d}" for n in (11, 15)
                       for d in (4096, 100_000, 1_000_000))
#: d past which the fused row's us_per_call/d must be non-increasing —
#: the two-level kernel's residency claim (below it, fixed plan/launch
#: costs still amortise, so per-coordinate cost legitimately falls)
MONOTONE_MIN_D = 100_000
#: fused must stay within this factor of the XLA substrate at the
#: deepest committed point (n=15, d=1e6) — the cliff-is-closed headline
FUSED_VS_XLA_MAX = 1.1
_KEY_RE = re.compile(r"^n=\d+,d=\d+$")
_BATCH_RE = re.compile(r"^b=\d+$")

AGG_TIME_SCHEMA = "rule -> 'n=<n>,d=<d>' -> us_per_call"
RESILIENCE_SCHEMA = "sim.resilience.v1"
RESILIENCE_FIELDS = ("honest_dev_mean", "honest_dev_max", "byz_mass_mean",
                     "final_loss", "loss_delta_post")
COMM_SCHEMA = "comm.v1"
COMM_FIELDS = ("wire_bytes", "bytes_per_worker", "us_per_call",
               "ratio_vs_fp32")
COMM_ORDER = ("fp32", "bf16", "qsgd:bits=8")   # strictly decreasing bytes
ACCURACY_SCHEMA = "accuracy.v1"
ACCURACY_FIELDS = ("acc_mean", "acc_std")
ANALYSIS_SCHEMA = "analysis.v1"
ANALYSIS_SECTIONS = ("lint", "contracts", "analysis")
ANALYSIS_KERNELS = ("fused_select", "pairwise_stats", "dequant_stats")
HIER_SCHEMA = "hier.v1"
HIER_FIELDS = ("us_per_call", "n_groups", "f_inner", "f_outer",
               "bytes_per_level")
HIER_ROWS = ("multi_bulyan[hier]", "multi_bulyan[flat]")
HIER_FLAT_FACTOR = 5.0          # flat must be >= this × hier at n >= 1024
HIER_BIG_N = 1024
_HIER_KEY_RE = re.compile(r"^n=(\d+),g=(\d+),d=(\d+)$")
SERVING_SCHEMA = "serving.v2"
SERVING_FIELDS = ("qps", "round_us", "round_us_p50", "round_us_p95",
                  "round_us_p99", "agg_us", "stale_rounds",
                  "reused_rounds", "f_defended_mean", "admitted_frac")
SERVING_ROWS = ("multi_bulyan[sync]", "multi_bulyan[async]")
_SERVING_KEY_RE = re.compile(r"^tau=(\d+),f=(\d+)$")


def _fail(msg: str) -> "list[str]":
    return [msg]


def _check_agg_time(path: str, results: dict) -> "list[str]":
    problems = []
    for rule, grid in results.items():
        if not isinstance(grid, dict) or not grid:
            problems.append(f"rule {rule!r}: empty or non-object grid")
            continue
        for key, us in grid.items():
            if not _KEY_RE.match(key):
                problems.append(f"rule {rule!r}: bad grid key {key!r} "
                                "(want 'n=<n>,d=<d>')")
            if not isinstance(us, (int, float)) or not math.isfinite(us) \
                    or us <= 0:
                problems.append(f"rule {rule!r} [{key}]: us_per_call must be "
                                f"a positive finite number, got {us!r}")
    # the grid-coverage and residency gates apply to full-grid payloads
    # only: a CI smoke run rewrites this file with a single shallow cell
    # (benchmarks/agg_time.py SMOKE_*), where a depth gate is vacuous.
    # Any fused cell at d >=
    # MONOTONE_MIN_D marks the payload full-grid.
    fused_cells = _cells_by_n(results.get("multi_bulyan[fused]", {}))
    full_grid = any(d >= MONOTONE_MIN_D
                    for pts in fused_cells.values() for d, _ in pts)
    for row in REQUIRED_ROWS:
        if row not in results:
            problems.append(f"missing required substrate row {row!r}")
            continue
        missing = [c for c in REQUIRED_CELLS if c not in results[row]]
        if missing and full_grid:
            problems.append(f"substrate row {row!r}: missing grid "
                            f"cell(s) {missing}")
    if full_grid:
        problems += _check_fused_monotone(results)
    return problems


def _cells_by_n(grid: dict) -> "dict[int, list[tuple[int, float]]]":
    by_n: dict = {}
    for key, us in grid.items():
        if not (_KEY_RE.match(key) and isinstance(us, (int, float))):
            continue
        kv = dict(p.split("=") for p in key.split(","))
        by_n.setdefault(int(kv["n"]), []).append((int(kv["d"]), us))
    return by_n


def _check_fused_monotone(results: dict) -> "list[str]":
    """The two-level residency gates on the measured fused row.

    * us_per_call/d non-increasing along d past ``MONOTONE_MIN_D`` for
      every n — per-coordinate cost must not degrade with depth (the
      single-level kernel failed exactly this: 0.79 us/coord at d=1e5
      vs 3.0 at d=1e6);
    * fused within ``FUSED_VS_XLA_MAX`` × the XLA substrate at the
      deepest point, n=15, d=1e6 — the fused path may never again be
      the reason to route deep applies to XLA.
    """
    problems = []
    fused = results.get("multi_bulyan[fused]", {})
    for n, pts in sorted(_cells_by_n(fused).items()):
        pts.sort()
        deep = [(d, us) for d, us in pts if d >= MONOTONE_MIN_D]
        for (d1, us1), (d2, us2) in zip(deep, deep[1:]):
            if us2 / d2 > us1 / d1:
                problems.append(
                    f"multi_bulyan[fused] n={n}: us_per_call/d grows from "
                    f"{us1 / d1:.3f} (d={d1}) to {us2 / d2:.3f} (d={d2}) "
                    "— the fused apply path is not monotone in d")
    xla = results.get("multi_bulyan[xla]", {})
    deepest = "n=15,d=1000000"
    f_us, x_us = fused.get(deepest), xla.get(deepest)
    if isinstance(f_us, (int, float)) and isinstance(x_us, (int, float)) \
            and x_us > 0 and f_us > FUSED_VS_XLA_MAX * x_us:
        problems.append(
            f"multi_bulyan[fused] [{deepest}]: {f_us:.0f} us > "
            f"{FUSED_VS_XLA_MAX}x the XLA substrate ({x_us:.0f} us) — "
            "the deep-grid cliff is back")
    return problems


def _check_resilience(path: str, results: dict) -> "list[str]":
    problems = []
    for rule, grid in results.items():
        if not isinstance(grid, dict) or not grid:
            problems.append(f"rule {rule!r}: empty or non-object attack grid")
            continue
        for attack, cell in grid.items():
            if not isinstance(cell, dict):
                problems.append(f"{rule}/{attack}: cell must be an object")
                continue
            missing = [f for f in RESILIENCE_FIELDS if f not in cell]
            if missing:
                problems.append(f"{rule}/{attack}: missing {missing}")
            for f in RESILIENCE_FIELDS:
                v = cell.get(f)
                if v is None:
                    continue
                if not isinstance(v, (int, float)) or not math.isfinite(v):
                    problems.append(f"{rule}/{attack}: {f} must be finite, "
                                    f"got {v!r}")
            bm = cell.get("byz_mass_mean")
            if isinstance(bm, (int, float)) and not 0.0 <= bm <= 1.0:
                problems.append(f"{rule}/{attack}: byz_mass_mean {bm} "
                                "outside [0, 1]")
            hd = cell.get("honest_dev_mean")
            if isinstance(hd, (int, float)) and hd < 0.0:
                problems.append(f"{rule}/{attack}: negative honest_dev_mean")
    return problems


def _check_comm(path: str, results: dict) -> "list[str]":
    problems = []
    for codec, grid in results.items():
        if not isinstance(grid, dict) or not grid:
            problems.append(f"codec {codec!r}: empty or non-object grid")
            continue
        for ckey, cell in grid.items():
            if not _KEY_RE.match(ckey):
                problems.append(f"codec {codec!r}: bad grid key {ckey!r} "
                                "(want 'n=<n>,d=<d>')")
            if not isinstance(cell, dict):
                problems.append(f"{codec}/{ckey}: cell must be an object")
                continue
            missing = [f for f in COMM_FIELDS if f not in cell]
            if missing:
                problems.append(f"{codec}/{ckey}: missing {missing}")
            for f in COMM_FIELDS:
                v = cell.get(f)
                if v is None:
                    continue
                if not isinstance(v, (int, float)) or not math.isfinite(v) \
                        or v <= 0:
                    problems.append(f"{codec}/{ckey}: {f} must be a "
                                    f"positive finite number, got {v!r}")
    missing_rows = [c for c in COMM_ORDER if c not in results]
    if missing_rows:
        problems.append(f"missing required codec row(s) {missing_rows} "
                        f"(the fp32 > bf16 > int8 ordering gate needs them)")
        return problems
    shared = set.intersection(*(set(results[c]) for c in COMM_ORDER))
    if len(shared) < 2:
        problems.append(
            f"need >= 2 shared (n, d) points across {COMM_ORDER}, "
            f"got {sorted(shared)}")
    for ckey in sorted(shared):
        sizes = [results[c][ckey].get("wire_bytes", 0) for c in COMM_ORDER]
        if not (isinstance(sizes[0], (int, float))
                and sizes[0] > sizes[1] > sizes[2] > 0):
            problems.append(
                f"[{ckey}]: wire_bytes not strictly ordered "
                f"fp32 > bf16 > qsgd int8: {dict(zip(COMM_ORDER, sizes))}")
    return problems


def _check_accuracy(path: str, results: dict) -> "list[str]":
    problems = []
    for rule, grid in results.items():
        if not isinstance(grid, dict) or not grid:
            problems.append(f"rule {rule!r}: empty or non-object grid")
            continue
        for bkey, cell in grid.items():
            if not _BATCH_RE.match(bkey):
                problems.append(f"rule {rule!r}: bad grid key {bkey!r} "
                                "(want 'b=<batch>')")
            if not isinstance(cell, dict):
                problems.append(f"{rule}/{bkey}: cell must be an object")
                continue
            missing = [f for f in ACCURACY_FIELDS if f not in cell]
            if missing:
                problems.append(f"{rule}/{bkey}: missing {missing}")
            acc = cell.get("acc_mean")
            if acc is not None and (not isinstance(acc, (int, float))
                                    or not 0.0 <= acc <= 1.0):
                problems.append(f"{rule}/{bkey}: acc_mean {acc!r} "
                                "outside [0, 1]")
            std = cell.get("acc_std")
            if std is not None and (not isinstance(std, (int, float))
                                    or std < 0.0 or not math.isfinite(std)):
                problems.append(f"{rule}/{bkey}: bad acc_std {std!r}")
    for rule in ("average", "multi_bulyan"):
        if rule not in results:
            problems.append(f"missing required rule row {rule!r}")
    return problems


def _check_hier(path: str, results: dict) -> "list[str]":
    problems = []
    for row in HIER_ROWS:
        if row not in results:
            problems.append(f"missing required hier row {row!r}")
    cells: dict = {}            # (row, n, g, d) -> cell
    for row, grid in results.items():
        if not isinstance(grid, dict) or not grid:
            problems.append(f"row {row!r}: empty or non-object grid")
            continue
        for key, cell in grid.items():
            m = _HIER_KEY_RE.match(key)
            if not m:
                problems.append(f"row {row!r}: bad grid key {key!r} "
                                "(want 'n=<n>,g=<g>,d=<d>')")
                continue
            if not isinstance(cell, dict):
                problems.append(f"{row}/{key}: cell must be an object")
                continue
            cells[(row,) + tuple(int(x) for x in m.groups())] = cell
            if "skipped" in cell:
                if not isinstance(cell["skipped"], str) or not cell["skipped"]:
                    problems.append(f"{row}/{key}: 'skipped' must carry a "
                                    "non-empty reason string")
                continue
            missing = [f for f in HIER_FIELDS if f not in cell]
            if missing:
                problems.append(f"{row}/{key}: missing {missing}")
            us = cell.get("us_per_call")
            if not isinstance(us, (int, float)) or not math.isfinite(us) \
                    or us <= 0:
                problems.append(f"{row}/{key}: us_per_call must be a "
                                f"positive finite number, got {us!r}")
            bpl = cell.get("bytes_per_level")
            if not (isinstance(bpl, list) and bpl
                    and all(isinstance(b, int) and b > 0 for b in bpl)):
                problems.append(f"{row}/{key}: bytes_per_level must be a "
                                f"non-empty list of positive ints, got {bpl!r}")
    hier = {(n, g, d): c for (row, n, g, d), c in cells.items()
            if row == "multi_bulyan[hier]" and "us_per_call" in c}
    flat = {(n, d): c for (row, n, g, d), c in cells.items()
            if row == "multi_bulyan[flat]"}
    if not hier:
        problems.append("no completed multi_bulyan[hier] cells")
        return problems
    # the scaling claim: at n >= 1024 the grouped path completes while the
    # flat path is skipped-as-infeasible or >= 5x slower
    for (n, g, d), hc in sorted(hier.items()):
        if n < HIER_BIG_N:
            continue
        fc = flat.get((n, d))
        if fc is None or "skipped" in fc:
            continue
        ratio = fc["us_per_call"] / max(hc["us_per_call"], 1e-9)
        if ratio < HIER_FLAT_FACTOR:
            problems.append(
                f"n={n},d={d}: flat path only {ratio:.1f}x the grouped "
                f"path (< {HIER_FLAT_FACTOR}x) and not skipped — the "
                "O(n·g) vs O(n²) claim does not hold")
    # O(n·g) ordering: with g and d fixed, grouped time must grow
    # subquadratically in n wherever the grid reaches n >= 1024
    by_gd: dict = {}
    for (n, g, d), hc in hier.items():
        by_gd.setdefault((g, d), []).append((n, hc["us_per_call"]))
    for (g, d), pts in sorted(by_gd.items()):
        pts.sort()
        for (n1, t1), (n2, t2) in zip(pts, pts[1:]):
            if n2 < HIER_BIG_N:
                continue
            quad = (n2 / n1) ** 2
            if t2 / max(t1, 1e-9) >= quad:
                problems.append(
                    f"g={g},d={d}: grouped time grows >= quadratically "
                    f"from n={n1} to n={n2} "
                    f"({t1:.0f} -> {t2:.0f} us, quadratic x{quad:.1f})")
    return problems


def _check_serving(path: str, results: dict) -> "list[str]":
    problems = []
    for row in SERVING_ROWS:
        if row not in results:
            problems.append(f"missing required serving row {row!r}")
    cells: dict = {}            # (row, tau, f) -> cell
    for row, grid in results.items():
        if not isinstance(grid, dict) or not grid:
            problems.append(f"row {row!r}: empty or non-object grid")
            continue
        for key, cell in grid.items():
            m = _SERVING_KEY_RE.match(key)
            if not m:
                problems.append(f"row {row!r}: bad grid key {key!r} "
                                "(want 'tau=<t>,f=<f>')")
                continue
            if not isinstance(cell, dict):
                problems.append(f"{row}/{key}: cell must be an object")
                continue
            cells[(row,) + tuple(int(x) for x in m.groups())] = cell
            missing = [f for f in SERVING_FIELDS if f not in cell]
            if missing:
                problems.append(f"{row}/{key}: missing {missing}")
            for f in ("qps", "round_us", "round_us_p50", "round_us_p95",
                      "round_us_p99"):
                v = cell.get(f)
                if not isinstance(v, (int, float)) or not math.isfinite(v) \
                        or v <= 0:
                    problems.append(f"{row}/{key}: {f} must be a positive "
                                    f"finite number, got {v!r}")
            ps = [cell.get(f) for f in ("round_us_p50", "round_us_p95",
                                        "round_us_p99")]
            if all(isinstance(p, (int, float)) for p in ps) and \
                    not ps[0] <= ps[1] <= ps[2]:
                problems.append(
                    f"{row}/{key}: percentiles not non-decreasing "
                    f"(p50={ps[0]!r}, p95={ps[1]!r}, p99={ps[2]!r})")
            af = cell.get("admitted_frac")
            if isinstance(af, (int, float)) and not 0.0 <= af <= 1.0:
                problems.append(f"{row}/{key}: admitted_frac {af} "
                                "outside [0, 1]")
    # the throughput claim: async strictly beats sync wherever the
    # byzantine contract is live and staleness is actually tolerated
    sync = {(t, f): c for (row, t, f), c in cells.items()
            if row == SERVING_ROWS[0]}
    asyn = {(t, f): c for (row, t, f), c in cells.items()
            if row == SERVING_ROWS[1]}
    live = [(t, f) for (t, f) in sorted(set(sync) & set(asyn))
            if t >= 1 and f > 0]
    if not live:
        problems.append("no shared (tau >= 1, f > 0) cell — the "
                        "async-beats-sync ordering gate has nothing to "
                        "check")
    for (t, f) in live:
        sq, aq = sync[(t, f)].get("qps"), asyn[(t, f)].get("qps")
        if not (isinstance(sq, (int, float)) and isinstance(aq, (int, float))
                and aq > sq):
            problems.append(
                f"tau={t},f={f}: async qps ({aq!r}) not strictly above "
                f"sync qps ({sq!r}) — the bounded-staleness buffer bought "
                "no throughput")
    return problems


def _check_analysis(path: str, results: dict) -> "list[str]":
    """The static-contract report: ships only when everything is proven."""
    problems = []
    missing = [s for s in ANALYSIS_SECTIONS if s not in results]
    if missing:
        return _fail(f"{path}: missing section(s) {missing}")
    for v in results["lint"].get("violations", [{"rule": "?"}]):
        problems.append(f"lint violation committed: {v.get('rule')} "
                        f"{v.get('path')}:{v.get('line')}: {v.get('msg')}")
    contracts = results["contracts"]
    if not contracts:
        problems.append("no contracts audited")
    for name, cell in contracts.items():
        if cell.get("status") != "proven":
            problems.append(f"contract {name}: status "
                            f"{cell.get('status')!r}, want 'proven' "
                            f"({'; '.join(cell.get('violations', []))})")
    analysis = results["analysis"]
    for kernel in ANALYSIS_KERNELS:
        grid = analysis.get("kernels", {}).get(kernel)
        if not grid:
            problems.append(f"missing kernel estimates for {kernel!r}")
            continue
        for key, est in grid.items():
            if not _KEY_RE.match(key):
                problems.append(f"{kernel}: bad grid key {key!r}")
            for f in ("d_tile", "macro_tile", "windows", "grid_steps",
                      "vmem_bytes", "hbm_read_bytes"):
                v = est.get(f)
                if not isinstance(v, int) or v <= 0:
                    problems.append(f"{kernel}/{key}: {f} must be a "
                                    f"positive int, got {v!r}")
    traffic = analysis.get("traffic_linearity", {})
    if not traffic.get("holds"):
        problems.append("vmem traffic-linearity diagnosis does not hold: "
                        f"{traffic.get('detail')!r}")
    d1e6 = analysis.get("kernels", {}).get("fused_select", {}) \
        .get("n=15,d=1000000")
    if not (d1e6 and d1e6.get("over_budget")
            and not d1e6.get("tile_over_budget")
            and d1e6.get("macro_tile", 0) > d1e6.get("d_tile", 0)):
        problems.append("fused_select n=15,d=1e6 must tile (over_budget), "
                        "fit per macro step, and run a multi-window macro "
                        "block — the two-level residency claim fails")
    return problems


def check(path: str) -> "list[str]":
    """Return a list of problems (empty = valid)."""
    try:
        with open(path) as fh:
            payload = json.load(fh)
    except FileNotFoundError:
        return _fail(f"{path}: missing — run `python -m benchmarks.run`")
    except json.JSONDecodeError as e:
        return _fail(f"{path}: not valid JSON ({e})")
    if not isinstance(payload, dict) or "results" not in payload:
        return _fail(f"{path}: top level must be an object with 'results'")
    problems = []
    if "schema" not in payload:
        problems.append(f"{path}: missing 'schema' field")
    results = payload["results"]
    if not isinstance(results, dict) or not results:
        return _fail(f"{path}: 'results' must be a non-empty object")
    schema = payload.get("schema")
    if schema == RESILIENCE_SCHEMA:
        problems += _check_resilience(path, results)
    elif schema == COMM_SCHEMA:
        problems += _check_comm(path, results)
    elif schema == ACCURACY_SCHEMA:
        problems += _check_accuracy(path, results)
    elif schema == HIER_SCHEMA:
        problems += _check_hier(path, results)
    elif schema == SERVING_SCHEMA:
        problems += _check_serving(path, results)
    elif schema == ANALYSIS_SCHEMA:
        problems += _check_analysis(path, results)
    elif schema == AGG_TIME_SCHEMA or schema is None:
        # None: legacy agg_time files predate the schema tag — still
        # validate the grid, with the missing-field problem noted above
        problems += _check_agg_time(path, results)
    else:
        problems.append(
            f"{path}: unrecognised schema {schema!r}; known: "
            f"{[AGG_TIME_SCHEMA, RESILIENCE_SCHEMA, COMM_SCHEMA, ACCURACY_SCHEMA, HIER_SCHEMA, SERVING_SCHEMA, ANALYSIS_SCHEMA]}")
    return problems


def main() -> None:
    paths = sys.argv[1:] or ["BENCH_agg_time.json"]
    failed = False
    for path in paths:
        problems = check(path)
        if problems:
            failed = True
            for p in problems:
                print(f"BENCH check FAILED: {p}", file=sys.stderr)
            continue
        with open(path) as fh:
            n_rows = len(json.load(fh)["results"])
        print(f"{path}: OK ({n_rows} rules)")
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
