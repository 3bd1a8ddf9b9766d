"""Pallas TPU kernel: fully fused BULYAN apply phase (two-level grid).

The unfused pipeline materialises both (θ, d) intermediates in HBM:

    g_ext = w_ext @ G     # HBM write, θ·d fp32
    g_agr = w_agr @ G     # HBM write, θ·d fp32
    out   = coord_select(g_ext, g_agr, β)   # HBM read of both, write d

— three O(θ·d) HBM round-trips that dominate the memory-bound roofline
(kernels/coord_select.py header).  This kernel fuses the whole apply phase
so the only HBM traffic is the one read of the stack and the (d,) output
write — the same traffic plain averaging pays, which is the paper's
m/n-slowdown claim made literal.

Two-level grid
--------------
The outer Pallas grid walks **macro-tiles** of ``macro_tile`` lanes.  Each
macro step brings one (n, macro_tile) block of the gradient stack plus the
small replicated (θ, n) extraction / aggregate weight matrices into VMEM,
then an inner ``fori_loop`` sweeps ``macro_tile // d_tile`` lane windows of
``d_tile`` each, running the einsum → median → β-selection → mean pipeline
per window.  The weights are read from their VMEM refs **once per macro
step**, not once per window — the per-step operand re-fetch plus dispatch
overhead is exactly the term that made the single-level kernel lose to XLA
past ~40 grid steps (the BENCH_agg_time.json d=1e6 cliff).  The inner loop
is a single traced body, so its per-window cost is pure compute.

Bitwise invariance: every pipeline stage is **column-independent** — the
einsums contract over the worker axis and the median / rank-by-counting /
masked mean act per coordinate — so any (macro_tile, d_tile) partition of
the lane axis produces bit-identical output to any other, including the
single-level ``macro_tile == d_tile`` layout.  Tested over the PR-2 edge
grid in tests/test_kernels.py.

VMEM per macro step: 2 · n·macro_tile·4 B for the double-buffered stack
block, (2θ + ~3θ²)·d_tile·4 B for the per-window einsum outputs and
rank-counting broadcasts, plus 2·θ·n·4 B for the resident weights.
``kernels/ops.two_level_tiles`` sizes (macro_tile, d_tile) against this
budget.

Numerics match ``core.gar.bulyan_coordinate_phase`` composed with the
weight einsums bit-for-bit in interpret mode (tested in
tests/test_substrates.py): the θ-axis median picks by rank count the
values the reference's stable sort picks, ties in the β-selection break by
row index, and the masked mean adds the rows in the reference's order
(``kernels/coord_select.coordinate_phase``).  The worker axis is not
padded: each block spans all n rows, so the kernel reads the (n, d) stack
as it is and no padded copy of it is made.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.coord_select import coordinate_phase

Array = jax.Array


def _select_tile(x, we, wa, *, beta: int):
    """The per-window pipeline: (n, dt) fp32 tile + resident weights
    -> (dt,) aggregate.  Column-independent — see module header."""
    # extraction einsums — MXU, contraction over the worker axis.  HIGHEST:
    # ext feeds the median/selection, so it must not lose bits to bf16-pass
    # matmuls on TPU (same rationale as core.api.leaf_sqdist_contrib).
    ext = jax.lax.dot_general(
        we, x, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)          # (theta, dt)
    agr = jax.lax.dot_general(
        wa, x, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)          # (theta, dt)

    # coordinate phase — VPU, the math of coord_select.py's kernel
    return coordinate_phase(ext, agr, beta)


def _kernel(x_ref, we_ref, wa_ref, o_ref, *, beta: int, d_tile: int,
            windows: int):
    # One read of the replicated weight pair per MACRO step; the inner
    # windows all close over the loaded values.
    we = we_ref[...]                                 # (theta, n) fp32
    wa = wa_ref[...]

    def window(j, carry):
        x = x_ref[:, pl.ds(j * d_tile, d_tile)].astype(jnp.float32)
        o_ref[0, pl.ds(j * d_tile, d_tile)] = _select_tile(
            x, we, wa, beta=beta)
        return carry

    if windows == 1:
        # single-window macro: skip the loop machinery entirely — this is
        # the exact single-level kernel body, kept as the trace for small d
        window(0, 0)
    else:
        jax.lax.fori_loop(0, windows, window, 0)


@functools.lru_cache(maxsize=256)
def _build_call(n: int, dp: int, theta: int, beta: int, d_tile: int,
                macro_tile: int, interpret: bool):
    """Cached pallas_call builder keyed on the fully resolved launch config.

    Building the call (closing the BlockSpecs over the geometry) is
    pure Python; caching it means repeat launches at the same geometry —
    every trainer step — skip the spec construction and reuse one callable
    identity, which also keeps the surrounding jit caches warm.
    """
    windows = macro_tile // d_tile
    return pl.pallas_call(
        functools.partial(_kernel, beta=beta, d_tile=d_tile,
                          windows=windows),
        grid=(pl.cdiv(dp, macro_tile),),
        in_specs=[
            pl.BlockSpec((n, macro_tile), lambda i: (0, i)),
            pl.BlockSpec((theta, n), lambda i: (0, 0)),
            pl.BlockSpec((theta, n), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, macro_tile), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, dp), jnp.float32),
        interpret=interpret,
        name="fused_select",
    )


def fused_select_pallas(x: Array, w_ext: Array, w_agr: Array, beta: int, *,
                        d_tile: int = 2048, macro_tile: int | None = None,
                        interpret: bool = False) -> Array:
    """(n, d) stack + (θ, n) plan weights -> (d,) fp32 Bulyan aggregate.

    ``macro_tile`` (a multiple of ``d_tile``; default ``d_tile`` — the
    single-level layout) sets the outer-grid block width.  Output is
    bitwise-invariant to the choice (column independence — module header).

    Past one macro block the lane axis is padded only to a multiple of
    128, never to one of ``macro_tile``: the last macro block may be
    partial.  Its lanes past the stack hold whatever the block buffer
    held, and since every stage is column-independent they reach only
    output lanes that are never written back.  So the grid costs no copy
    of a large stack whose d is a multiple of 128.
    """
    if x.ndim != 2:
        raise ValueError(f"x must be (n, d), got shape {x.shape}")
    n, d = x.shape
    if w_ext.shape != w_agr.shape:
        raise ValueError(
            f"weight shapes differ: {w_ext.shape} vs {w_agr.shape}")
    if w_ext.ndim != 2 or w_ext.shape[1] != n:
        raise ValueError(
            f"weights must be (theta, n={n}), got {w_ext.shape}")
    theta = w_ext.shape[0]
    if not 1 <= beta <= theta:
        raise ValueError(f"need 1 <= beta <= theta, got beta={beta}, "
                         f"theta={theta}")
    d_tile = min(d_tile, max(128, ((d - 1) // 128 + 1) * 128))
    if macro_tile is None:
        macro_tile = d_tile
    if macro_tile % d_tile:
        raise ValueError(f"macro_tile {macro_tile} must be a multiple of "
                         f"d_tile {d_tile}")
    # never carry more macro than the (padded) operand has lanes — d_cap is
    # a d_tile multiple, so the clamp preserves the divisibility invariant
    d_cap = ((d - 1) // d_tile + 1) * d_tile
    macro_tile = min(macro_tile, d_cap)
    # an operand narrower than one macro block is padded to it; a wider
    # one only to the lane width, its last block then partial
    d_pad = (-d) % (macro_tile if d < macro_tile else 128)
    if d_pad:
        x = jnp.pad(x, ((0, 0), (0, d_pad)))
    # pad/cast hoisted: only cast when the dtype actually differs — a fp32
    # caller (every plan produced by core.gar) pays no per-call convert op
    if w_ext.dtype != jnp.float32:
        w_ext = w_ext.astype(jnp.float32)
    if w_agr.dtype != jnp.float32:
        w_agr = w_agr.astype(jnp.float32)
    call = _build_call(n, x.shape[1], theta, beta, d_tile, macro_tile,
                       interpret)
    out = call(x, w_ext, w_agr)
    return out[0, :d]
