"""Pallas TPU kernels: fused dequantize → pairwise statistics.

The wire (repro.comm) hands the aggregator *quantized* payloads — int8
QSGD/sign levels or bf16 rows — plus a per-worker dequant multiplier.  The
unfused pipeline would materialise the fp32 (n, d) stack in HBM
(``decode`` = payload · mult), then stream it back through
``pairwise_stats``: two O(n·d) HBM round-trips of the *widened* data, 4–8×
the payload's own footprint.  This kernel extends the PR-2 single-pass
stats contract one layer down the memory hierarchy: each macro step loads
one ``(n, macro_tile)`` *payload* block HBM→VMEM (1–2 B/coordinate — the
wire format is also the HBM format), and an inner ``fori_loop`` widens and
scales one ``d_tile`` window at a time in VMEM, emitting the window's raw
distance contribution (MXU gram) and squared-norm rows (VPU) exactly like
``pairwise_sqdist._stats_kernel``.  The fp32 stack never exists in HBM.

Bitwise contract (DESIGN.md §9): the in-VMEM dequantize is *exactly* the
codec's decode — ``payload.astype(f32) * mult[row]`` — and the wrapper in
``kernels/ops.py`` derives ``d_tile`` with the same autotune call
``pairwise_stats`` uses for the decoded fp32 stack, so window boundaries
and per-window float summation match decode-then-``pairwise_stats`` bit
for bit in interpret mode (tested on the PR-2 edge-shape grid in
tests/test_comm.py).  The two-level layout preserves the single-level
global window order (init at window 0, left-associated accumulation
after), so ``macro_tile`` is bitwise-free, same as
``pairwise_sqdist.pairwise_stats_pallas``.

The rectangular variant (``dequant_stats_rect_pallas``) is the §10 shard
kernel for encoded wires: an (n_loc, d) payload block contracted against
the gathered (n, d) payload — O(n_loc·n·d) per device — bitwise-identical
to the matching rows of the square kernel at the same ``d_tile``.

Row padding follows the payload dtype's sublane tile (int8 → 32, bf16 →
16, else 8); padded rows carry zero payload *and* zero multiplier, so
their contributions vanish and the ``[:n, :n]`` slice is exact.  The
distance output is raw (unclamped, diagonal kept) for cross-leaf
accumulation — finalise with ``core.api.finalize_dists``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

Array = jax.Array

_SUBLANES = {jnp.int8.dtype: 32, jnp.bfloat16.dtype: 16}


def _kernel(p_ref, s_ref, d_ref, o_ref, *, d_tile: int, windows: int):
    """One macro step: dequantize ``windows`` payload windows in VMEM and
    contribute each window's distances AND norms from the single macro
    transfer.  Global window order matches the single-level kernel."""
    i = pl.program_id(0)
    mult = s_ref[...][0]                              # (n,) — resident

    def window(j, carry):
        p = p_ref[:, pl.ds(j * d_tile, d_tile)]
        # the codec decode, in VMEM: widen then one multiply per element
        x = p.astype(jnp.float32) * mult[:, None]     # (n, d_tile)
        # HIGHEST: score order decides selection (same rationale as
        # pairwise_sqdist._stats_kernel, whose math this mirrors exactly)
        gram = jax.lax.dot_general(
            x, x, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)       # (n, n) — MXU
        sq = jnp.sum(x * x, axis=1)                   # (n,)   — VPU
        tile = sq[:, None] + sq[None, :] - 2.0 * gram
        first = jnp.logical_and(i == 0, j == 0)

        @pl.when(first)
        def _init():
            d_ref[...] = tile
            o_ref[...] = sq[None, :]

        @pl.when(jnp.logical_not(first))
        def _acc():
            d_ref[...] += tile
            o_ref[...] += sq[None, :]

        return carry

    if windows == 1:
        window(0, 0)
    else:
        jax.lax.fori_loop(0, windows, window, 0)


def dequant_stats_pallas(payload: Array, mult: Array, *, d_tile: int = 2048,
                         macro_tile: int | None = None,
                         interpret: bool = False):
    """(n, d) quantized payload + (n,) row multipliers ->
    ((n, n) raw sq-dists, (n,) sq-norms) of the *decoded* rows.

    ``payload`` is int8 or bfloat16 (fp32 accepted for the identity
    multiplier path); ``mult`` is the codec's per-row dequant multiplier.
    Pads the worker axis to the payload dtype's sublane tile and d up to a
    multiple of ``macro_tile`` (zero payload × zero mult padding is exact).
    """
    if payload.ndim != 2:
        raise ValueError(f"payload must be (n, d), got {payload.shape}")
    n, d = payload.shape
    if mult.shape != (n,):
        raise ValueError(f"mult must be ({n},), got {mult.shape}")
    sublane = _SUBLANES.get(payload.dtype, 8)
    n_pad = (-n) % sublane
    d_tile = min(d_tile, max(128, ((d - 1) // 128 + 1) * 128))
    if macro_tile is None:
        macro_tile = d_tile
    if macro_tile % d_tile:
        raise ValueError(f"macro_tile {macro_tile} must be a multiple of "
                         f"d_tile {d_tile}")
    macro_tile = min(macro_tile, ((d - 1) // d_tile + 1) * d_tile)
    d_pad = (-d) % macro_tile
    if n_pad or d_pad:
        payload = jnp.pad(payload, ((0, n_pad), (0, d_pad)))
    if n_pad:
        mult = jnp.pad(mult, (0, n_pad))
    np_, dp = payload.shape
    dists, norms = pl.pallas_call(
        functools.partial(_kernel, d_tile=d_tile,
                          windows=macro_tile // d_tile),
        grid=(dp // macro_tile,),
        in_specs=[pl.BlockSpec((np_, macro_tile), lambda i: (0, i)),
                  pl.BlockSpec((1, np_), lambda i: (0, 0))],
        out_specs=(pl.BlockSpec((np_, np_), lambda i: (0, 0)),
                   pl.BlockSpec((1, np_), lambda i: (0, 0))),
        out_shape=(jax.ShapeDtypeStruct((np_, np_), jnp.float32),
                   jax.ShapeDtypeStruct((1, np_), jnp.float32)),
        interpret=interpret,
        name="dequant_stats",
    )(payload, mult.astype(jnp.float32)[None, :])
    return dists[:n, :n], norms[0, :n]


def _rect_kernel(pl_ref, ml_ref, pf_ref, mf_ref, d_ref, o_ref, *,
                 d_tile: int, windows: int):
    i = pl.program_id(0)
    m_loc = ml_ref[...][0]                            # (n_loc,)
    m_full = mf_ref[...][0]                           # (n,)

    def window(j, carry):
        sl = pl.ds(j * d_tile, d_tile)
        xl = pl_ref[:, sl].astype(jnp.float32) * m_loc[:, None]
        xf = pf_ref[:, sl].astype(jnp.float32) * m_full[:, None]
        gram = jax.lax.dot_general(
            xl, xf, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)       # (n_loc, n)
        sq_f = jnp.sum(xf * xf, axis=1)               # (n,)
        sq_l = jnp.sum(xl * xl, axis=1)               # (n_loc,)
        tile = sq_l[:, None] + sq_f[None, :] - 2.0 * gram
        first = jnp.logical_and(i == 0, j == 0)

        @pl.when(first)
        def _init():
            d_ref[...] = tile
            o_ref[...] = sq_f[None, :]

        @pl.when(jnp.logical_not(first))
        def _acc():
            d_ref[...] += tile
            o_ref[...] += sq_f[None, :]

        return carry

    if windows == 1:
        window(0, 0)
    else:
        jax.lax.fori_loop(0, windows, window, 0)


def dequant_stats_rect_pallas(p_loc: Array, m_loc: Array, p_full: Array,
                              m_full: Array, *, d_tile: int = 2048,
                              macro_tile: int | None = None,
                              interpret: bool = False):
    """Rectangular fused dequantize → stats: (n_loc, d) payload block +
    (n_loc,) multipliers × gathered (n, d) payload + (n,) multipliers ->
    ((n_loc, n) raw sq-dist block, (n,) sq-norms) of the decoded rows.

    At the same ``d_tile`` the block is bitwise-identical to the matching
    rows of :func:`dequant_stats_pallas` on the full payload (row-subset
    decode is elementwise, row-subset gemm and row-wise norms are
    deterministic per row).  Padded local rows (zero payload × zero mult)
    are dropped by the ``[:n_loc]`` slice.
    """
    if p_loc.ndim != 2 or p_full.ndim != 2:
        raise ValueError(f"need 2-d payloads, got {p_loc.shape} / "
                         f"{p_full.shape}")
    n_loc, d = p_loc.shape
    n, d_f = p_full.shape
    if d != d_f:
        raise ValueError(f"lane axes differ: {d} vs {d_f}")
    if m_loc.shape != (n_loc,):
        raise ValueError(f"m_loc must be ({n_loc},), got {m_loc.shape}")
    if m_full.shape != (n,):
        raise ValueError(f"m_full must be ({n},), got {m_full.shape}")
    if p_loc.dtype != p_full.dtype:
        raise ValueError(f"payload dtypes differ: {p_loc.dtype} vs "
                         f"{p_full.dtype}")
    sublane = _SUBLANES.get(p_full.dtype, 8)
    l_pad = (-n_loc) % sublane
    n_pad = (-n) % sublane
    d_tile = min(d_tile, max(128, ((d - 1) // 128 + 1) * 128))
    if macro_tile is None:
        macro_tile = d_tile
    if macro_tile % d_tile:
        raise ValueError(f"macro_tile {macro_tile} must be a multiple of "
                         f"d_tile {d_tile}")
    macro_tile = min(macro_tile, ((d - 1) // d_tile + 1) * d_tile)
    d_pad = (-d) % macro_tile
    if l_pad or d_pad:
        p_loc = jnp.pad(p_loc, ((0, l_pad), (0, d_pad)))
    if l_pad:
        m_loc = jnp.pad(m_loc, (0, l_pad))
    if n_pad or d_pad:
        p_full = jnp.pad(p_full, ((0, n_pad), (0, d_pad)))
    if n_pad:
        m_full = jnp.pad(m_full, (0, n_pad))
    lp, dp = p_loc.shape
    np_ = p_full.shape[0]
    dists, norms = pl.pallas_call(
        functools.partial(_rect_kernel, d_tile=d_tile,
                          windows=macro_tile // d_tile),
        grid=(dp // macro_tile,),
        in_specs=[pl.BlockSpec((lp, macro_tile), lambda i: (0, i)),
                  pl.BlockSpec((1, lp), lambda i: (0, 0)),
                  pl.BlockSpec((np_, macro_tile), lambda i: (0, i)),
                  pl.BlockSpec((1, np_), lambda i: (0, 0))],
        out_specs=(pl.BlockSpec((lp, np_), lambda i: (0, 0)),
                   pl.BlockSpec((1, np_), lambda i: (0, 0))),
        out_shape=(jax.ShapeDtypeStruct((lp, np_), jnp.float32),
                   jax.ShapeDtypeStruct((1, np_), jnp.float32)),
        interpret=interpret,
        name="dequant_stats_rect",
    )(p_loc, m_loc.astype(jnp.float32)[None, :],
      p_full, m_full.astype(jnp.float32)[None, :])
    return dists[:n_loc, :n], norms[0, :n]
