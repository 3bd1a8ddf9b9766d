"""Jit'd public wrappers for the Pallas kernels.

On CPU (this container) the kernels execute in ``interpret=True`` mode —
the kernel body runs in Python on the same BlockSpec schedule, which is the
validation story for the TPU target.  On TPU backends the compiled kernels
run as written.

Backend resolution happens *outside* the jit boundary: ``interpret`` is a
static argument of every jitted wrapper, so the backend choice is part of
the jit cache key instead of being baked into a trace that silently goes
stale when the default backend changes (e.g. a CPU-traced interpret=True
call surviving into a TPU run).  Scope: this protects the wrappers' own
jit caches (eager callers).  A caller that jits a whole train/serve step
traces these wrappers inline, so resolution happens at *that* trace's
time under ordinary jit semantics — pass ``interpret`` explicitly from
step-construction code if the step must pin a backend choice.

Tile policy
-----------
All streaming kernels are **two-level**: the outer Pallas grid walks
``macro_tile``-lane blocks of the stack (one HBM→VMEM transfer and one
read of the replicated operands per block), and an inner traced loop
sweeps ``d_tile``-lane compute windows inside each block.  The inner
``d_tile`` keeps per-window intermediates (rank-counting broadcasts, fp32
widenings) small; the outer ``macro_tile`` is what amortises the per-grid-
step dispatch + operand-re-read overhead that made deep single-level grids
lose to XLA at d = 1e6 (the retired ``DEEP_GRID_STEPS`` lift treated the
symptom by fattening single-level tiles; the two-level grid removes the
per-step re-read term entirely, so the hot path is monotone in d).

:func:`two_level_tiles` sizes the pair against the VMEM budget: per macro
step the working set is ``2·(rows+out_rows)·4·macro_tile`` (double-
buffered streamed lanes) + ``(scratch_rows+rows)·4·d_tile`` (per-window
intermediates incl. the fp32 widening of the current window) +
``fixed_bytes`` (replicated weights / resident accumulators).  The policy
minimises outer grid steps, breaking ties toward the larger ``d_tile``.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.coord_select import coord_select_pallas
from repro.kernels.dequant_stats import (dequant_stats_pallas,
                                         dequant_stats_rect_pallas)
from repro.kernels.fused_select import fused_select_pallas
from repro.kernels.pairwise_sqdist import (pairwise_sqdist_pallas,
                                           pairwise_stats_pallas,
                                           pairwise_stats_rect_pallas)
from repro.obs import profile as _prof

Array = jax.Array

# Conservative per-step working-set budget: half of a v5e core's ~16 MB
# VMEM, leaving headroom for Pallas' input double buffering and the
# replicated small operands.
VMEM_BUDGET_BYTES = 8 * 1024 * 1024
_MAX_D_TILE = 8192
#: narrowest inner window :func:`two_level_tiles` will pick while a wider
#: one fits — sub-2048-lane windows measured up to ~1.7× slower at
#: d = 1e6 (the per-window loop overhead beats the one or two outer grid
#: steps the taller macro block saves)
_MIN_D_TILE = 2048


def autotune_d_tile(rows: int, d: int, *, scratch_rows: int = 0,
                    fixed_bytes: int = 0,
                    vmem_budget: int = VMEM_BUDGET_BYTES,
                    max_tile: int = _MAX_D_TILE) -> int:
    """Largest d_tile (multiple of 128) fitting the VMEM budget.

    ``rows`` counts the fp32 (rows, d_tile) *operand* buffers per grid step
    (double-buffered by Pallas — a 2x factor models that);
    ``scratch_rows`` counts 4-byte rows of in-kernel intermediates that
    scale with the tile width but are not double-buffered (e.g. the
    (θ, θ, d_tile) rank-counting broadcasts of the selection kernels);
    ``fixed_bytes`` covers tile-width-independent residents (the (n, n)
    accumulator, replicated weights).  Clamped to [128, max_tile] and to d
    rounded up to the 128-lane boundary — a tile wider than the padded
    operand only adds dead lanes.

    This sizes the *inner* compute window; :func:`two_level_tiles` sizes
    the (d_tile, macro_tile) pair jointly for the two-level kernels.
    """
    if rows <= 0:
        raise ValueError(f"rows must be positive, got {rows}")
    if d <= 0:
        raise ValueError(f"d must be positive, got {d}")
    d_cap = ((d - 1) // 128 + 1) * 128
    budget = max(0, vmem_budget - fixed_bytes)
    per_lane = (2 * rows + scratch_rows) * 4
    tile = (budget // per_lane // 128) * 128
    return max(128, min(tile, max_tile, d_cap))


def _select_scratch_rows(theta: int) -> int:
    """Tile-width-scaling intermediates of the selection kernels: the three
    (θ, θ) int32 rank-counting broadcasts (lt/eq/rank) plus a few fp32
    (θ,)-row temporaries (ext/agr/rank/dist)."""
    return 3 * theta * theta + 4 * theta


def two_level_macro(rows: int, d: int, d_tile: int, *,
                    out_rows: int = 1, scratch_rows: int = 0,
                    fixed_bytes: int = 0,
                    vmem_budget: int = VMEM_BUDGET_BYTES) -> int:
    """Largest macro_tile (multiple of ``d_tile``) fitting the VMEM budget.

    Per macro step: ``2·(rows+out_rows)·4·macro`` bytes of double-buffered
    streamed lanes (the stack block plus the streamed output rows — pass
    ``out_rows=0`` when the outputs are grid-resident accumulators and
    already counted in ``fixed_bytes``), ``(scratch_rows+rows)·4·d_tile``
    per-window intermediates (the ``+rows`` is the fp32 widening of the
    current window), and ``fixed_bytes`` of residents.  Clamped to at
    least one window and to d rounded up to the ``d_tile`` boundary.
    """
    d_cap = ((d - 1) // d_tile + 1) * d_tile
    rem = vmem_budget - fixed_bytes - (scratch_rows + rows) * 4 * d_tile
    lanes = rem // (2 * (rows + out_rows) * 4)
    macro = (lanes // d_tile) * d_tile
    return max(d_tile, min(macro, d_cap))


def two_level_tiles(rows: int, d: int, *, out_rows: int = 1,
                    scratch_rows: int = 0, fixed_bytes: int = 0,
                    vmem_budget: int = VMEM_BUDGET_BYTES,
                    max_tile: int = _MAX_D_TILE) -> Tuple[int, int]:
    """Joint (d_tile, macro_tile) policy for the two-level kernels.

    Sweeps lane-aligned power-of-two inner windows (128·2^k ≤ max_tile),
    sizes the largest budget-fitting macro block for each
    (:func:`two_level_macro`), and picks the pair that minimises outer
    grid steps — the per-step dispatch/operand-re-read overhead is the
    measured cost driver in both interpret and compiled modes — breaking
    ties toward the larger ``d_tile`` (fewer inner iterations for the
    same transfer schedule).
    """
    if rows <= 0:
        raise ValueError(f"rows must be positive, got {rows}")
    if d <= 0:
        raise ValueError(f"d must be positive, got {d}")
    d_cap = ((d - 1) // 128 + 1) * 128
    fits = []
    dt = 128
    while dt <= min(max_tile, d_cap):
        rem = (vmem_budget - fixed_bytes
               - (scratch_rows + rows) * 4 * dt)
        if rem >= 2 * (rows + out_rows) * 4 * dt:
            macro = two_level_macro(rows, d, dt, out_rows=out_rows,
                                    scratch_rows=scratch_rows,
                                    fixed_bytes=fixed_bytes,
                                    vmem_budget=vmem_budget)
            fits.append((dt, macro))
        dt *= 2
    if not fits:
        # degenerate budget: fall back to the minimal lane-aligned window
        return 128, 128
    # sub-1024-lane windows only when nothing wider fits the budget (or
    # the operand itself is narrower): the per-window loop overhead of
    # tiny windows outweighs the one or two outer steps they save
    wide = [c for c in fits if c[0] >= _MIN_D_TILE]
    best = None
    for dt, macro in (wide or fits):
        key = (-(-d // macro), -dt)
        if best is None or key <= best[0]:
            best = (key, dt, macro)
    return best[1], best[2]


def fused_select_tiles(n_rows: int, d: int, theta: int) -> Tuple[int, int]:
    """The fused_select (d_tile, macro_tile) policy.

    ``n_rows`` is the sublane-padded worker count.  Scratch is the
    selection pipeline's rank-counting broadcasts
    (:func:`_select_scratch_rows`); fixed bytes are the VMEM-resident
    (θ, n) weight pair.  Shared by the :func:`fused_select` wrapper and
    ``analysis/vmem.estimate_fused_select`` — one policy, one cost model.
    """
    return two_level_tiles(n_rows, d, out_rows=1,
                           scratch_rows=_select_scratch_rows(theta),
                           fixed_bytes=2 * theta * n_rows * 4)


def stats_macro_tile(n_rows: int, d: int, d_tile: int, *,
                     fixed_bytes: int) -> int:
    """The stats kernels' macro policy: the inner ``d_tile`` is pinned to
    the single-level autotune value — tile boundaries ARE the float
    accumulation order of the (n, n)/(n,) accumulators, so changing them
    would break bitwise parity with the committed artifacts — and only the
    outer macro block is sized from the residual budget.  The (n, n)
    accumulator and norm row are grid-resident (``out_rows=0``; they are
    part of ``fixed_bytes``)."""
    return two_level_macro(n_rows, d, d_tile, out_rows=0,
                           fixed_bytes=fixed_bytes)


def _interpret() -> bool:
    """Compiled on a TPU, interpreted on the CPU (the tests); any other
    backend is an error, never a silent interpreter run."""
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(
            f"the Pallas kernels run on 'tpu' (compiled) or 'cpu' "
            f"(interpreted), not on {backend!r}")
    return backend == "cpu"


def _resolve(interpret: Optional[bool]) -> bool:
    return _interpret() if interpret is None else bool(interpret)


@functools.partial(jax.jit, static_argnames=("d_tile", "interpret"))
def _pairwise_sqdist(x: Array, *, d_tile: int, interpret: bool) -> Array:
    return pairwise_sqdist_pallas(x, d_tile=d_tile, interpret=interpret)


def pairwise_sqdist(x: Array, *, d_tile: Optional[int] = None,
                    interpret: Optional[bool] = None) -> Array:
    """(n, d) -> (n, n) fp32 squared distances (Pallas)."""
    if d_tile is None:
        n_rows = x.shape[0] + (-x.shape[0]) % 8
        d_tile = autotune_d_tile(n_rows, x.shape[1],
                                 fixed_bytes=n_rows * n_rows * 4)
    return _pairwise_sqdist(x, d_tile=d_tile, interpret=_resolve(interpret))


def _stats_tiles(n_rows: int, d: int) -> Tuple[int, int]:
    """(d_tile, macro_tile) for the square stats kernels: the PR-2
    autotune inner window (bitwise-pinned — see :func:`stats_macro_tile`)
    plus the residency macro."""
    fixed = n_rows * (n_rows + 8) * 4
    d_tile = autotune_d_tile(n_rows, d, fixed_bytes=fixed)
    return d_tile, stats_macro_tile(n_rows, d, d_tile, fixed_bytes=fixed)


@functools.partial(jax.jit,
                   static_argnames=("d_tile", "macro_tile", "interpret"))
def _pairwise_stats(x: Array, *, d_tile: int, macro_tile: int,
                    interpret: bool) -> Tuple[Array, Array]:
    return pairwise_stats_pallas(x, d_tile=d_tile, macro_tile=macro_tile,
                                 interpret=interpret)


def pairwise_stats(x: Array, *, d_tile: Optional[int] = None,
                   macro_tile: Optional[int] = None,
                   interpret: Optional[bool] = None) -> Tuple[Array, Array]:
    """Single-pass (n, d) -> ((n, n) raw sq-dists, (n,) sq-norms).

    One HBM read of the stack feeds both outputs; the distance matrix is
    raw (unclamped, diagonal not zeroed) for cross-leaf accumulation —
    finalise with ``core.api.finalize_dists``.
    """
    n_rows = x.shape[0] + (-x.shape[0]) % 8
    if d_tile is None:
        d_tile, auto_macro = _stats_tiles(n_rows, x.shape[1])
        if macro_tile is None:
            macro_tile = auto_macro
    elif macro_tile is None:
        macro_tile = d_tile
    _prof.record_kernel("pairwise_stats", n=x.shape[0], d=x.shape[1],
                        d_tile=d_tile, macro_tile=macro_tile)
    return _pairwise_stats(x, d_tile=d_tile, macro_tile=macro_tile,
                           interpret=_resolve(interpret))


@functools.partial(jax.jit,
                   static_argnames=("d_tile", "macro_tile", "interpret"))
def _pairwise_stats_rect(x_loc: Array, x_full: Array, *, d_tile: int,
                         macro_tile: int,
                         interpret: bool) -> Tuple[Array, Array]:
    return pairwise_stats_rect_pallas(x_loc, x_full, d_tile=d_tile,
                                      macro_tile=macro_tile,
                                      interpret=interpret)


def pairwise_stats_rect(x_loc: Array, x_full: Array, *,
                        d_tile: Optional[int] = None,
                        macro_tile: Optional[int] = None,
                        interpret: Optional[bool] = None
                        ) -> Tuple[Array, Array]:
    """Rectangular stats: (n_loc, d) row block × (n, d) gathered stack ->
    ((n_loc, n) raw sq-dist block, (n,) sq-norms).

    The §10 shard kernel: each device contracts only its own row block
    against the gathered stack — O(n_loc·n·d) instead of the square
    kernel's redundant O(n²·d) per device.  The default ``d_tile`` is the
    SAME autotune value :func:`pairwise_stats` derives for the full stack:
    identical tile boundaries (plus row-subset gemm determinism) make the
    block bitwise-identical to the matching rows of the square kernel
    (tests/test_kernels.py), which is what keeps ``sharded_raw_stats``
    bitwise-equal to the replicated path.
    """
    n_full = x_full.shape[0] + (-x_full.shape[0]) % 8
    n_loc = x_loc.shape[0] + (-x_loc.shape[0]) % 8
    if d_tile is None:
        d_tile, _ = _stats_tiles(n_full, x_full.shape[1])
    if macro_tile is None:
        fixed = (n_loc * n_full + n_loc * (n_full + 8)) * 4
        macro_tile = stats_macro_tile(n_loc + n_full, x_full.shape[1],
                                      d_tile, fixed_bytes=fixed)
    _prof.record_kernel("pairwise_stats_rect", n=x_full.shape[0],
                        d=x_full.shape[1], d_tile=d_tile,
                        macro_tile=macro_tile, n_loc=x_loc.shape[0])
    return _pairwise_stats_rect(x_loc, x_full, d_tile=d_tile,
                                macro_tile=macro_tile,
                                interpret=_resolve(interpret))


def _dequant_tiles(n_rows: int, d: int) -> Tuple[int, int]:
    """Same autotune call :func:`pairwise_stats` makes for the decoded
    fp32 stack — identical tile boundaries keep the float accumulation
    order, and therefore bitwise parity with decode-then-stats, intact
    (DESIGN.md §9)."""
    return _stats_tiles(n_rows, d)


@functools.partial(jax.jit,
                   static_argnames=("d_tile", "macro_tile", "interpret"))
def _dequant_stats(payload: Array, mult: Array, *, d_tile: int,
                   macro_tile: int,
                   interpret: bool) -> Tuple[Array, Array]:
    return dequant_stats_pallas(payload, mult, d_tile=d_tile,
                                macro_tile=macro_tile, interpret=interpret)


def dequant_stats(payload: Array, mult: Array, *,
                  d_tile: Optional[int] = None,
                  macro_tile: Optional[int] = None,
                  interpret: Optional[bool] = None) -> Tuple[Array, Array]:
    """Fused dequantize → single-pass stats on a quantized (n, d) payload.

    ``payload`` int8/bf16 + ``mult`` (n,) per-row dequant multipliers ->
    ((n, n) raw sq-dists, (n,) sq-norms) of the decoded rows, without the
    fp32 stack ever existing in HBM.  The default ``d_tile`` is the SAME
    autotune call :func:`pairwise_stats` makes for the decoded fp32 stack:
    identical tile boundaries keep the float accumulation order — and
    therefore bitwise parity with decode-then-``pairwise_stats`` in
    interpret mode — intact (DESIGN.md §9).
    """
    n_rows = payload.shape[0] + (-payload.shape[0]) % 8
    if d_tile is None:
        d_tile, auto_macro = _dequant_tiles(n_rows, payload.shape[1])
        if macro_tile is None:
            macro_tile = auto_macro
    elif macro_tile is None:
        macro_tile = d_tile
    _prof.record_kernel("dequant_stats", n=payload.shape[0],
                        d=payload.shape[1], d_tile=d_tile,
                        macro_tile=macro_tile, dtype=str(payload.dtype))
    return _dequant_stats(payload, mult, d_tile=d_tile,
                          macro_tile=macro_tile,
                          interpret=_resolve(interpret))


@functools.partial(jax.jit,
                   static_argnames=("d_tile", "macro_tile", "interpret"))
def _dequant_stats_rect(p_loc: Array, m_loc: Array, p_full: Array,
                        m_full: Array, *, d_tile: int, macro_tile: int,
                        interpret: bool) -> Tuple[Array, Array]:
    return dequant_stats_rect_pallas(p_loc, m_loc, p_full, m_full,
                                     d_tile=d_tile, macro_tile=macro_tile,
                                     interpret=interpret)


def dequant_stats_rect(p_loc: Array, m_loc: Array, p_full: Array,
                       m_full: Array, *, d_tile: Optional[int] = None,
                       macro_tile: Optional[int] = None,
                       interpret: Optional[bool] = None
                       ) -> Tuple[Array, Array]:
    """Rectangular fused dequantize → stats: (n_loc, d) payload block ×
    (n, d) gathered payload -> ((n_loc, n) raw sq-dist block, (n,)
    sq-norms) of the decoded rows.

    The encoded-wire counterpart of :func:`pairwise_stats_rect`; the
    default ``d_tile`` matches the square :func:`dequant_stats` autotune
    for the full payload so the block is bitwise-identical to the
    matching rows of the square kernel (tests/test_comm.py).
    """
    n_full = p_full.shape[0] + (-p_full.shape[0]) % 8
    if d_tile is None:
        d_tile, _ = _dequant_tiles(n_full, p_full.shape[1])
    if macro_tile is None:
        n_loc = p_loc.shape[0] + (-p_loc.shape[0]) % 8
        fixed = (n_loc * n_full + n_loc * (n_full + 8)) * 4
        macro_tile = stats_macro_tile(n_loc + n_full, p_full.shape[1],
                                      d_tile, fixed_bytes=fixed)
    _prof.record_kernel("dequant_stats_rect", n=p_full.shape[0],
                        d=p_full.shape[1], d_tile=d_tile,
                        macro_tile=macro_tile, n_loc=p_loc.shape[0],
                        dtype=str(p_full.dtype))
    return _dequant_stats_rect(p_loc, m_loc, p_full, m_full,
                               d_tile=d_tile, macro_tile=macro_tile,
                               interpret=_resolve(interpret))


@functools.partial(jax.jit, static_argnames=("beta", "d_tile", "interpret"))
def _coord_select(g_ext: Array, g_agr: Array, *, beta: int, d_tile: int,
                  interpret: bool) -> Array:
    return coord_select_pallas(g_ext, g_agr, beta, d_tile=d_tile,
                               interpret=interpret)


def coord_select(g_ext: Array, g_agr: Array, beta: int, *,
                 d_tile: Optional[int] = None,
                 interpret: Optional[bool] = None) -> Array:
    """Fused Bulyan coordinate phase (Pallas) on materialised (θ, d) inputs."""
    if d_tile is None:
        theta = g_agr.shape[0]
        d_tile = autotune_d_tile(2 * theta, g_agr.shape[1],
                                 scratch_rows=_select_scratch_rows(theta))
    return _coord_select(g_ext, g_agr, beta=beta, d_tile=d_tile,
                         interpret=_resolve(interpret))


@functools.partial(jax.jit,
                   static_argnames=("beta", "d_tile", "macro_tile",
                                    "interpret"))
def _fused_select(x: Array, w_ext: Array, w_agr: Array, *, beta: int,
                  d_tile: int, macro_tile: int, interpret: bool) -> Array:
    return fused_select_pallas(x, w_ext, w_agr, beta, d_tile=d_tile,
                               macro_tile=macro_tile, interpret=interpret)


def fused_select(x: Array, w_ext: Array, w_agr: Array, beta: int, *,
                 d_tile: Optional[int] = None,
                 macro_tile: Optional[int] = None,
                 interpret: Optional[bool] = None) -> Array:
    """Fully fused Bulyan apply: (n, d) stack + (θ, n) plan -> (d,).

    Extraction einsums, median, β-selection and mean all happen in VMEM —
    no (θ, d) HBM intermediates (see kernels/fused_select.py).  The
    two-level (d_tile, macro_tile) launch geometry comes from
    :func:`fused_select_tiles`; the output is bitwise-invariant to it.
    """
    if d_tile is None:
        n_rows = x.shape[0] + (-x.shape[0]) % 8
        d_tile, auto_macro = fused_select_tiles(n_rows, x.shape[1],
                                                w_ext.shape[0])
        if macro_tile is None:
            macro_tile = auto_macro
    elif macro_tile is None:
        macro_tile = d_tile
    _prof.record_kernel("fused_select", n=x.shape[0], d=x.shape[1],
                        d_tile=d_tile, macro_tile=macro_tile,
                        theta=w_ext.shape[0])
    return _fused_select(x, w_ext, w_agr, beta=beta, d_tile=d_tile,
                         macro_tile=macro_tile, interpret=_resolve(interpret))
