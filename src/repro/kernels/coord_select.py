"""Pallas TPU kernel: fused BULYAN coordinate phase.

Per coordinate j (Algorithm 1 lines 21-24): median of the θ extracted
winners, then the average of the β entries of the θ aggregates closest to
that median.  Embarrassingly parallel over coordinates → grid over d-tiles,
each step loads two (θ, d_tile) blocks into VMEM and writes a (1, d_tile)
output row.  θ ≤ n − 2f − 2 is small (≤ 32 on our meshes), so both the
median and the β-smallest selection rank the θ rows by counting (O(θ²)
compares, which vectorise on the VPU; Pallas has no TPU lowering for
``sort``) and stay register/VMEM-local.  :func:`coordinate_phase` is that
math, shared with ``kernels/fused_select.py``; it picks the values the
stable sorts of ``core.gar.bulyan_coordinate_phase`` pick, and sums them
in the same order, so the two agree bit for bit.

Fusing median + selection + masked mean into one kernel avoids three (θ, d)
HBM round-trips of the unfused XLA path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

Array = jax.Array


def rank_rows(v):
    """Per-column rank of each row of a (θ, dt) tile: its position in a
    stable ascending sort of the column, counted as
    ``#{k: v[k] < v[i]} + #{k < i: v[k] == v[i]}`` under the sort's order:
    ties go to the smaller index, -0.0 == 0.0, and NaN sorts last."""
    theta = v.shape[0]
    other, this = v[None, :, :], v[:, None, :]       # row k vs row i
    nan_o, nan_t = other != other, this != this
    lt = (other < this) | (nan_t & ~nan_o)
    eq = (other == this) | (nan_t & nan_o)
    row = jax.lax.broadcasted_iota(jnp.int32, (theta, theta, 1), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (theta, theta, 1), 1)
    before = lt | (eq & (col < row))
    return jnp.sum(before.astype(jnp.int32), axis=1)  # (theta, dt)


def _row_of_rank(v, rank, r: int):
    """The row ranked ``r`` in each column, by a select chain: exact, so
    -0.0 and ±inf come through as stored.  Ranks are a permutation of
    0..θ-1 per column, so exactly one row matches."""
    out = v[0]
    for i in range(1, v.shape[0]):
        out = jnp.where(rank[i] == r, v[i], out)
    return out


def median_rows(v):
    """(θ, dt) -> (dt,) column median; the values ``core.gar._median_axis0``
    takes from its stable sort."""
    theta = v.shape[0]
    rank = rank_rows(v)
    hi = _row_of_rank(v, rank, theta // 2)
    if theta % 2:
        return hi
    return 0.5 * (_row_of_rank(v, rank, theta // 2 - 1) + hi)


def coordinate_phase(ext, agr, beta: int):
    """(θ, dt) fp32 extracted winners and aggregates -> (dt,) Bulyan
    coordinate phase: the mean of the β ``agr`` entries closest to the
    median of ``ext``."""
    med = median_rows(ext)
    dist = jnp.abs(agr - med[None, :])               # (theta, dt)
    sel = rank_rows(dist) < beta
    acc = jnp.where(sel[0], agr[0], 0.0)
    for i in range(1, agr.shape[0]):                 # rows in index order
        acc = acc + jnp.where(sel[i], agr[i], 0.0)
    return acc / float(beta)


def _kernel(ext_ref, agr_ref, o_ref, *, beta: int):
    ext = ext_ref[...].astype(jnp.float32)           # (theta, dt)
    agr = agr_ref[...].astype(jnp.float32)           # (theta, dt)
    o_ref[...] = coordinate_phase(ext, agr, beta)[None, :]


def coord_select_pallas(g_ext: Array, g_agr: Array, beta: int, *,
                        d_tile: int = 2048, interpret: bool = False) -> Array:
    """(theta, d) x2 -> (d,) fp32 fused coordinate phase."""
    if g_ext.shape != g_agr.shape:
        raise ValueError(
            f"g_ext/g_agr shapes differ: {g_ext.shape} vs {g_agr.shape}")
    if g_agr.ndim != 2:
        raise ValueError(f"expected (theta, d) inputs, got {g_agr.shape}")
    theta, d = g_agr.shape
    if not 1 <= beta <= theta:
        raise ValueError(
            f"need 1 <= beta <= theta, got beta={beta}, theta={theta}")
    d_tile = min(d_tile, max(128, ((d - 1) // 128 + 1) * 128))
    d_pad = (-d) % d_tile
    if d_pad:
        g_ext = jnp.pad(g_ext, ((0, 0), (0, d_pad)))
        g_agr = jnp.pad(g_agr, ((0, 0), (0, d_pad)))
    dp = g_agr.shape[1]
    grid = (dp // d_tile,)
    out = pl.pallas_call(
        functools.partial(_kernel, beta=beta),
        grid=grid,
        in_specs=[
            pl.BlockSpec((theta, d_tile), lambda i: (0, i)),
            pl.BlockSpec((theta, d_tile), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, d_tile), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, dp), jnp.float32),
        interpret=interpret,
        name="coord_select",
    )(g_ext, g_agr)
    return out[0, :d]
