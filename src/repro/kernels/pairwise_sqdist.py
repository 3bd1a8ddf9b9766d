"""Pallas TPU kernels: pairwise squared euclidean distances over d-tiles.

The paper's §V identifies the O(n²·d) pairwise-distance computation as the
dominant cost of (MULTI-)KRUM/BULYAN; its CUDA implementation was limited to
n ≤ 24 by on-die shared memory.  The TPU formulation (DESIGN.md §3/§6)
streams the (n, d) gradient matrix HBM→VMEM, computes per-window Gram
matrices on the MXU (``x @ x.T`` — the only O(n²·d) term) plus row norms on
the VPU, and accumulates ``‖a‖² + ‖b‖² − 2·gram`` into the (n, n) output
block, which stays resident in VMEM across the whole grid (output
revisiting).

Two-level grid (DESIGN.md §7): the outer Pallas grid walks
``macro_tile``-lane blocks — one HBM→VMEM transfer and one grid-step
dispatch per block — and an inner traced ``fori_loop`` sweeps
``d_tile``-lane compute windows inside the block.  Per-window float math
and the **global window order** are identical to the single-level kernel
(window g = i·windows + j initialises the accumulators at g = 0 and
accumulates left-associated after), so any ``macro_tile`` choice is
bitwise-identical to the committed single-level layout: extra zero-padded
windows at the tail add exact ``+0.0`` (squared terms are never −0.0).

The rectangular variant (``pairwise_stats_rect_pallas``) is the §10 shard
kernel: an (n_loc, d) row block contracted against the gathered (n, d)
stack — O(n_loc·n·d) per device instead of the square kernel's redundant
O(n²·d).  With the same ``d_tile`` boundaries, its output block is
bitwise-identical to the matching rows of the square kernel (row-subset
gemm and row-wise norms are deterministic per row), which is what lets
``core.api.sharded_raw_stats`` keep bitwise parity with the replicated
path (tests/test_spmd.py).

VMEM budget per macro step: n·macro_tile·4 B (streamed x block, double-
buffered) + n²·4 B (resident accumulator) + n·d_tile·4 B (the window's
fp32 widening).  ``kernels/ops.py`` sizes (d_tile, macro_tile) against
this; the MXU contraction dim is the d_tile axis → keep it a multiple of
128; n is padded to a multiple of 8 (sublanes).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

Array = jax.Array


def _kernel(x_ref, o_ref):
    i = pl.program_id(0)
    x = x_ref[...].astype(jnp.float32)              # (n, d_tile)
    gram = jax.lax.dot_general(
        x, x, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)          # (n, n) — MXU
    sq = jnp.sum(x * x, axis=1)                      # (n,)   — VPU
    tile = sq[:, None] + sq[None, :] - 2.0 * gram

    @pl.when(i == 0)
    def _init():
        o_ref[...] = tile

    @pl.when(i > 0)
    def _acc():
        o_ref[...] += tile


def pairwise_sqdist_pallas(x: Array, *, d_tile: int = 2048,
                           interpret: bool = False) -> Array:
    """(n, d) -> (n, n) fp32 squared distances (diagonal zeroed).

    Pads n up to a multiple of 8 and d up to a multiple of ``d_tile``
    (zero padding is exact for distances).
    """
    if x.ndim != 2:
        raise ValueError(f"x must be (n, d), got shape {x.shape}")
    n, d = x.shape
    n_pad = (-n) % 8
    d_tile = min(d_tile, max(128, ((d - 1) // 128 + 1) * 128))
    d_pad = (-d) % d_tile
    if n_pad or d_pad:
        x = jnp.pad(x, ((0, n_pad), (0, d_pad)))
    np_, dp = x.shape
    grid = (dp // d_tile,)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((np_, d_tile), lambda i: (0, i))],
        out_specs=pl.BlockSpec((np_, np_), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((np_, np_), jnp.float32),
        interpret=interpret,
        name="pairwise_sqdist",
    )(x)
    out = out[:n, :n]
    out = jnp.maximum(out, 0.0)
    return out * (1.0 - jnp.eye(n, dtype=jnp.float32))


def _stats_tile(x):
    """One window's (tile contribution, norm row) from a fp32 (rows, dt)
    view — the shared per-window math of all stats kernels."""
    # HIGHEST: score order decides selection — no bf16 passes on TPU
    gram = jax.lax.dot_general(
        x, x, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)          # (n, n) — MXU
    sq = jnp.sum(x * x, axis=1)                      # (n,)   — VPU
    return sq[:, None] + sq[None, :] - 2.0 * gram, sq


def _stats_kernel(x_ref, d_ref, s_ref, *, d_tile: int, windows: int):
    """One macro step: ``windows`` d-tile windows of distance AND norm
    contributions from a single VMEM transfer of the macro block.  Global
    window order matches the single-level kernel — bitwise contract in
    the module header."""
    i = pl.program_id(0)

    def window(j, carry):
        x = x_ref[:, pl.ds(j * d_tile, d_tile)].astype(jnp.float32)
        tile, sq = _stats_tile(x)
        first = jnp.logical_and(i == 0, j == 0)

        @pl.when(first)
        def _init():
            d_ref[...] = tile
            s_ref[...] = sq[None, :]

        @pl.when(jnp.logical_not(first))
        def _acc():
            d_ref[...] += tile
            s_ref[...] += sq[None, :]

        return carry

    if windows == 1:
        window(0, 0)
    else:
        jax.lax.fori_loop(0, windows, window, 0)


def pairwise_stats_pallas(x: Array, *, d_tile: int = 2048,
                          macro_tile: int | None = None,
                          interpret: bool = False):
    """Single-pass stats: (n, d) -> ((n, n) sq-dists, (n,) sq-norms).

    The unfused path reads the stack from HBM twice — once for the distance
    gram, once for the norms.  Both outputs here are accumulated from the
    same per-tile VMEM load, halving the stats phase's HBM traffic.  The
    distance matrix is raw (unclamped, diagonal not zeroed) so callers can
    accumulate contributions across leaves and finalise once
    (``core.api.finalize_dists``) — identical float summation to the
    single-output kernel, for every ``macro_tile`` (module header).
    """
    if x.ndim != 2:
        raise ValueError(f"x must be (n, d), got shape {x.shape}")
    n, d = x.shape
    n_pad = (-n) % 8
    d_tile = min(d_tile, max(128, ((d - 1) // 128 + 1) * 128))
    if macro_tile is None:
        macro_tile = d_tile
    if macro_tile % d_tile:
        raise ValueError(f"macro_tile {macro_tile} must be a multiple of "
                         f"d_tile {d_tile}")
    macro_tile = min(macro_tile, ((d - 1) // d_tile + 1) * d_tile)
    d_pad = (-d) % macro_tile
    if n_pad or d_pad:
        x = jnp.pad(x, ((0, n_pad), (0, d_pad)))
    np_, dp = x.shape
    dists, norms = pl.pallas_call(
        functools.partial(_stats_kernel, d_tile=d_tile,
                          windows=macro_tile // d_tile),
        grid=(dp // macro_tile,),
        in_specs=[pl.BlockSpec((np_, macro_tile), lambda i: (0, i))],
        out_specs=(pl.BlockSpec((np_, np_), lambda i: (0, 0)),
                   pl.BlockSpec((1, np_), lambda i: (0, 0))),
        out_shape=(jax.ShapeDtypeStruct((np_, np_), jnp.float32),
                   jax.ShapeDtypeStruct((1, np_), jnp.float32)),
        interpret=interpret,
        name="pairwise_stats",
    )(x)
    return dists[:n, :n], norms[0, :n]


def _rect_tile(xl, xf):
    """One window's rectangular (block contribution, full norm row)."""
    gram = jax.lax.dot_general(
        xl, xf, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)          # (n_loc, n) — MXU
    sq_f = jnp.sum(xf * xf, axis=1)                  # (n,)
    sq_l = jnp.sum(xl * xl, axis=1)                  # (n_loc,)
    return sq_l[:, None] + sq_f[None, :] - 2.0 * gram, sq_f


def _rect_kernel(xl_ref, xf_ref, d_ref, s_ref, *, d_tile: int,
                 windows: int):
    i = pl.program_id(0)

    def window(j, carry):
        sl = pl.ds(j * d_tile, d_tile)
        xl = xl_ref[:, sl].astype(jnp.float32)
        xf = xf_ref[:, sl].astype(jnp.float32)
        tile, sq_f = _rect_tile(xl, xf)
        first = jnp.logical_and(i == 0, j == 0)

        @pl.when(first)
        def _init():
            d_ref[...] = tile
            s_ref[...] = sq_f[None, :]

        @pl.when(jnp.logical_not(first))
        def _acc():
            d_ref[...] += tile
            s_ref[...] += sq_f[None, :]

        return carry

    if windows == 1:
        window(0, 0)
    else:
        jax.lax.fori_loop(0, windows, window, 0)


def pairwise_stats_rect_pallas(x_loc: Array, x_full: Array, *,
                               d_tile: int = 2048,
                               macro_tile: int | None = None,
                               interpret: bool = False):
    """Rectangular single-pass stats: (n_loc, d) row block × (n, d)
    gathered stack -> ((n_loc, n) raw sq-dist block, (n,) sq-norms).

    With the same ``d_tile`` the block is bitwise-identical to the
    matching rows of :func:`pairwise_stats_pallas` on the full stack
    (module header).  Both row axes zero-pad to a sublane multiple of 8;
    padded *local* rows produce garbage rows that the ``[:n_loc]`` slice
    drops (they never mix into real rows), padded *full* rows/columns are
    exact zeros.
    """
    if x_loc.ndim != 2 or x_full.ndim != 2:
        raise ValueError(f"need 2-d operands, got {x_loc.shape} / "
                         f"{x_full.shape}")
    n_loc, d = x_loc.shape
    n, d_f = x_full.shape
    if d != d_f:
        raise ValueError(f"lane axes differ: {d} vs {d_f}")
    l_pad = (-n_loc) % 8
    n_pad = (-n) % 8
    d_tile = min(d_tile, max(128, ((d - 1) // 128 + 1) * 128))
    if macro_tile is None:
        macro_tile = d_tile
    if macro_tile % d_tile:
        raise ValueError(f"macro_tile {macro_tile} must be a multiple of "
                         f"d_tile {d_tile}")
    macro_tile = min(macro_tile, ((d - 1) // d_tile + 1) * d_tile)
    d_pad = (-d) % macro_tile
    if l_pad or d_pad:
        x_loc = jnp.pad(x_loc, ((0, l_pad), (0, d_pad)))
    if n_pad or d_pad:
        x_full = jnp.pad(x_full, ((0, n_pad), (0, d_pad)))
    lp, dp = x_loc.shape
    np_ = x_full.shape[0]
    dists, norms = pl.pallas_call(
        functools.partial(_rect_kernel, d_tile=d_tile,
                          windows=macro_tile // d_tile),
        grid=(dp // macro_tile,),
        in_specs=[pl.BlockSpec((lp, macro_tile), lambda i: (0, i)),
                  pl.BlockSpec((np_, macro_tile), lambda i: (0, i))],
        out_specs=(pl.BlockSpec((lp, np_), lambda i: (0, 0)),
                   pl.BlockSpec((1, np_), lambda i: (0, 0))),
        out_shape=(jax.ShapeDtypeStruct((lp, np_), jnp.float32),
                   jax.ShapeDtypeStruct((1, np_), jnp.float32)),
        interpret=interpret,
        name="pairwise_stats_rect",
    )(x_loc, x_full)
    return dists[:n_loc, :n], norms[0, :n]
