"""Static per-macro-step VMEM / HBM-traffic estimator for the Pallas kernels.

Mirrors the exact BlockSpec/grid arithmetic of ``kernels/ops.py`` — the
padding, the two-level ``(d_tile, macro_tile)`` policies
(``fused_select_tiles`` / ``_stats_tiles``) and ``_select_scratch_rows``
are *called*, not re-derived, so the estimate and the tile policy can
never drift apart silently (that agreement is the §12 cross-check).

For each kernel × (n, d) point the estimator emits the chosen inner
``d_tile`` and outer ``macro_tile``, the outer grid depth, the per-macro-
step VMEM working set (double-buffered streamed lanes + per-window
intermediates + fixed residents — the same model ``two_level_macro``
budgets against) and the HBM read/write traffic, plus two diagnoses:

* ``over_budget`` — the *full-d* working set exceeds the VMEM budget, so
  the kernel must tile (always true for the benchmark-scale stacks);
* ``tile_over_budget`` — even a single macro step busts the budget
  (never true for a policy-chosen launch; flags hand-picked tiles).

The single-level era's ``grid_bound`` diagnosis is retired with the cliff
it described: the fused kernel re-fetched its replicated (θ, n) weight
pair once per ``d_tile``-wide grid step, so past ~40 steps the per-step
dispatch + re-read overhead beat the byte savings (the measured d=1e6
loss).  The two-level kernels read the replicated operands once per
``macro_tile`` block — the re-read term shrinks by ``macro/d_tile`` (≥
an order of magnitude at benchmark scale) and the grid depth at d = 1e6
drops from ~123 steps to ~21, so the hot path stays traffic-bound:
:func:`diagnose_traffic_linearity` checks that claim against the
committed benchmark.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional

from repro.kernels import ops

_PAYLOAD_ITEMSIZE = {"int8": 1, "bfloat16": 2}


def f_for_bench(n: int) -> int:
    """The benchmark grid's f convention (benchmarks/agg_time.py)."""
    return max(1, (n - 3) // 4)


def _pad(x: int, m: int) -> int:
    return x + (-x) % m


@dataclasses.dataclass(frozen=True)
class KernelEstimate:
    """Static footprint of one kernel launch at one (n, d) point."""

    kernel: str
    n: int
    d: int
    d_tile: int              # inner compute window
    macro_tile: int          # outer streamed block (== d_tile: single-level)
    windows: int             # inner d_tile windows per macro step
    grid_steps: int          # OUTER grid depth (macro blocks)
    vmem_bytes: int          # per-macro-step working set
    vmem_budget: int
    hbm_read_bytes: int
    hbm_write_bytes: int
    over_budget: bool        # full-d working set > budget (must tile)
    tile_over_budget: bool   # even a single macro step busts the budget

    def to_json(self) -> dict:
        return dataclasses.asdict(self)


def _finish(kernel: str, n: int, d: int, d_tile: int, macro_tile: int,
            rows: int, out_rows: int, scratch_rows: int, fixed_bytes: int,
            read_fn, write_bytes: int) -> KernelEstimate:
    """Assemble the estimate from the tile policy's own cost model.

    Per macro step: ``2·(rows+out_rows)·4·macro`` double-buffered streamed
    lanes + ``(scratch_rows+rows)·4·d_tile`` per-window intermediates
    (incl. the fp32 widening of the current window) + ``fixed_bytes``
    residents — byte-for-byte the ``ops.two_level_macro`` budget.
    ``read_fn(d_pad, grid)`` gives the HBM read bytes for the padded
    stack at the *outer* grid depth.
    """
    if macro_tile % d_tile:
        raise ValueError(
            f"macro_tile {macro_tile} not a multiple of d_tile {d_tile}")
    grid = -(-d // macro_tile)
    d_pad = grid * macro_tile
    stream = 2 * (rows + out_rows) * 4
    window = (scratch_rows + rows) * 4 * d_tile
    vmem = stream * macro_tile + window + fixed_bytes
    vmem_full = stream * d_pad + window + fixed_bytes
    return KernelEstimate(
        kernel=kernel, n=n, d=d, d_tile=d_tile, macro_tile=macro_tile,
        windows=macro_tile // d_tile, grid_steps=grid,
        vmem_bytes=vmem, vmem_budget=ops.VMEM_BUDGET_BYTES,
        hbm_read_bytes=read_fn(d_pad, grid), hbm_write_bytes=write_bytes,
        over_budget=vmem_full > ops.VMEM_BUDGET_BYTES,
        tile_over_budget=vmem > ops.VMEM_BUDGET_BYTES)


def estimate_fused_select(n: int, d: int, *, f: Optional[int] = None,
                          d_tile: Optional[int] = None,
                          macro_tile: Optional[int] = None
                          ) -> KernelEstimate:
    """Fused Bulyan apply: (n, d) stack + two (θ, n) plans -> (d,)."""
    f = f_for_bench(n) if f is None else f
    theta = n - 2 * f - 2
    if theta < 1:
        raise ValueError(f"n={n}, f={f}: theta={theta} < 1")
    n_pad = _pad(n, 8)
    scratch = ops._select_scratch_rows(theta)
    fixed = 2 * theta * n_pad * 4
    if d_tile is None:
        # the wrapper's own two-level policy — the estimate must live on
        # the exact (d_tile, macro_tile) pair the kernel launches with
        d_tile, auto_macro = ops.fused_select_tiles(n_pad, d, theta)
        if macro_tile is None:
            macro_tile = auto_macro
    elif macro_tile is None:
        macro_tile = d_tile
    # x streams once; the replicated (θ, n) weight pair is fetched once
    # per OUTER grid step (constant index_map on the macro grid) — the
    # residual of the retired per-d_tile re-read term, now amortised over
    # macro_tile lanes; the (1, macro) output block writes back per step.
    return _finish(
        "fused_select", n, d, d_tile, macro_tile,
        rows=n_pad, out_rows=1, scratch_rows=scratch, fixed_bytes=fixed,
        read_fn=lambda d_pad, grid: n_pad * d_pad * 4 + grid * fixed,
        write_bytes=_pad(d, macro_tile) * 4)


def estimate_pairwise_stats(n: int, d: int, *,
                            d_tile: Optional[int] = None,
                            macro_tile: Optional[int] = None
                            ) -> KernelEstimate:
    """Single-pass stats: (n, d) -> ((n, n) raw sq-dists, (n,) norms)."""
    n_pad = _pad(n, 8)
    fixed = n_pad * (n_pad + 8) * 4       # resident (n, n) acc + norms row
    if d_tile is None:
        # same policy call the wrapper makes: the inner tile is the PR-2
        # autotune value (tile boundaries ARE the float accumulation
        # order), only the macro block is new
        d_tile, auto_macro = ops._stats_tiles(n_pad, d)
        if macro_tile is None:
            macro_tile = auto_macro
    elif macro_tile is None:
        macro_tile = d_tile
    # accumulators are grid-resident (out_rows=0, counted in fixed); the
    # stack streams exactly once — no per-step re-read term at all
    return _finish(
        "pairwise_stats", n, d, d_tile, macro_tile,
        rows=n_pad, out_rows=0, scratch_rows=0, fixed_bytes=fixed,
        read_fn=lambda d_pad, grid: n_pad * d_pad * 4,
        write_bytes=(n_pad * n_pad + n_pad) * 4)


def estimate_dequant_stats(n: int, d: int, *, dtype: str = "int8",
                           d_tile: Optional[int] = None,
                           macro_tile: Optional[int] = None
                           ) -> KernelEstimate:
    """Fused dequantize→stats on an (n, d) int8/bf16 payload."""
    if dtype not in _PAYLOAD_ITEMSIZE:
        raise ValueError(f"payload dtype must be one of "
                         f"{sorted(_PAYLOAD_ITEMSIZE)}, got {dtype!r}")
    item = _PAYLOAD_ITEMSIZE[dtype]
    n_pad = _pad(n, 8)
    fixed = n_pad * (n_pad + 8) * 4
    if d_tile is None:
        # _dequant_tiles == _stats_tiles: the tile is budgeted for the
        # *decoded* fp32 rows so the accumulation order (and bitwise
        # parity with decode-then-pairwise_stats) is preserved (§9)
        d_tile, auto_macro = ops._dequant_tiles(n_pad, d)
        if macro_tile is None:
            macro_tile = auto_macro
    elif macro_tile is None:
        macro_tile = d_tile
    # payload blocks stream at the narrow itemsize; the widened fp32 rows
    # live only in VMEM, one d_tile window at a time — modelled by the
    # same (scratch+rows)·d_tile term the policy budgets
    return _finish(
        "dequant_stats", n, d, d_tile, macro_tile,
        rows=n_pad, out_rows=0, scratch_rows=0, fixed_bytes=fixed,
        read_fn=lambda d_pad, grid: n_pad * d_pad * item + n_pad * 4,
        write_bytes=(n_pad * n_pad + n_pad) * 4)


_ESTIMATORS = {
    "fused_select": estimate_fused_select,
    "pairwise_stats": estimate_pairwise_stats,
    "dequant_stats": estimate_dequant_stats,
}


def estimate(kernel: str, n: int, d: int, **kw) -> KernelEstimate:
    if kernel not in _ESTIMATORS:
        raise ValueError(f"unknown kernel {kernel!r}; "
                         f"known: {sorted(_ESTIMATORS)}")
    return _ESTIMATORS[kernel](n, d, **kw)


def bench_points(bench_results: dict, row: str = "multi_bulyan[fused]"
                 ) -> List[Dict]:
    """The committed (n, d) grid points of one BENCH_agg_time.json row."""
    pts = []
    for key, us in sorted(bench_results.get(row, {}).items()):
        kv = dict(p.split("=") for p in key.split(","))
        pts.append({"key": key, "n": int(kv["n"]), "d": int(kv["d"]),
                    "us_per_call": us})
    return pts


def diagnose_traffic_linearity(bench_results: dict,
                               row: str = "multi_bulyan[fused]") -> Dict:
    """The cliff-is-closed check: fused cost must track HBM traffic in d.

    Estimates every committed ``multi_bulyan[fused]`` point and computes
    its achieved bytes-per-µs.  The single-level cliff's signature was
    throughput *collapsing* with depth — at n=15 the d=1e6 point moved
    10× the bytes of d=1e5 but ran 38× longer.  With operand residency
    the deep points must sustain their bandwidth: for each n, the
    largest-d point's bytes-per-µs must be within 2× of the best point
    of that n (small-d points are allowed to be overhead-dominated in
    the *other* direction — a fixed plan/launch cost over few bytes —
    which is amortisation, not a cliff).  Replaces the retired
    ``diagnose_cliff``, whose grid-bound/2×-slowdown split described the
    single-level re-read regime.
    """
    pts = bench_points(bench_results, row)
    if not pts:
        return {"points": [], "holds": False,
                "detail": f"no {row} row in benchmark"}
    for p in pts:
        est = estimate_fused_select(p["n"], p["d"])
        p["estimate"] = est.to_json()
        p["bytes"] = est.hbm_read_bytes + est.hbm_write_bytes
        p["bytes_per_us"] = p["bytes"] / p["us_per_call"]
    log_bw = sum(math.log(p["bytes_per_us"]) for p in pts) / len(pts)
    holds = True
    by_n: Dict[int, List[Dict]] = {}
    for p in pts:
        by_n.setdefault(p["n"], []).append(p)
    for n, group in sorted(by_n.items()):
        peak = max(p["bytes_per_us"] for p in group)
        deepest = max(group, key=lambda p: p["d"])
        for p in group:
            p["throughput_vs_peak"] = p["bytes_per_us"] / peak
            p["deepest"] = p is deepest
            # only the deepest point carries the cliff claim; shallower
            # points are reported but not gated
            p["consistent"] = (p["throughput_vs_peak"] >= 0.5
                               if p is deepest else True)
            holds = holds and p["consistent"]
    return {"points": pts, "bytes_per_us": math.exp(log_bw), "holds": holds,
            "detail": "deepest-d point per n sustains >=0.5x the peak "
                      "measured bytes/us of that n — cost stays linear "
                      "in traffic, no deep-grid cliff"}
