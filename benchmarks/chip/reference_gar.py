"""Plain multi-Bulyan, the benchmark's own (El Mhamdi et al. 2018,
Algorithm 1 with multi-Krum aggregates; Rouault et al. 2019).

Distances are float32 grams summed in float64 on the host, the plan is
the literal iterated multi-Krum in float64 (ties by index), and the
coordinate phase runs in float32.  ``precision`` names how every matrix
product is computed: ``"f32"`` (float32, ``HIGHEST``) for the reference,
``"bf16_3x"`` for its control, one step below: each operand split into
two bfloat16 parts and three of the four products kept, which is what
``Precision.HIGH`` does on a TPU, spelled out so that it rounds alike on
any backend.  Nothing of the program is imported.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np


def _param_axes(x) -> tuple:
    return tuple(range(1, x.ndim))


def _split(x):
    hi = x.astype(jnp.bfloat16).astype(jnp.float32)
    return hi, (x - hi).astype(jnp.bfloat16).astype(jnp.float32)


def tensordot(a, b, axes, precision: str):
    """``jnp.tensordot`` computed as ``precision`` names."""
    def td(x, y):
        return jnp.tensordot(x, y, axes=axes,
                             precision=jax.lax.Precision.HIGHEST)
    if precision == "f32":
        return td(a, b)
    if precision == "bf16_3x":
        (ah, al), (bh, bl) = _split(a), _split(b)
        return td(ah, bh) + (td(ah, bl) + td(al, bh))
    raise ValueError(f"unknown precision {precision!r}")


@functools.lru_cache(maxsize=None)
def _leaf_stats(precision: str):
    def stats(x):
        axes = _param_axes(x)
        gram = tensordot(x, x, (axes, axes), precision)
        return gram, jnp.sum(x * x, axis=axes)
    return jax.jit(stats)


def distances(leaves, precision) -> np.ndarray:
    """(n, n) squared distances over every coordinate of ``leaves``."""
    n = leaves[0].shape[0]
    total = np.zeros((n, n), np.float64)
    for x in leaves:
        gram, sq = (np.asarray(a, np.float64)
                    for a in _leaf_stats(precision)(x))
        total += sq[:, None] + sq[None, :] - 2.0 * gram
    total = np.maximum(total, 0.0)
    np.fill_diagonal(total, 0.0)
    return total


def plan(dists: np.ndarray, f: int):
    """Iterated multi-Krum: ``(w_ext, w_agr, beta)``, each weight matrix
    (theta, n) float32; round r keeps the winner's row in ``w_ext`` and
    the mean of the m_r = k - f - 2 best-scored rows in ``w_agr``."""
    n = dists.shape[0]
    theta = n - 2 * f - 2
    beta = theta - 2 * f
    if beta < 1:
        raise ValueError(f"multi-Bulyan needs n >= 4f + 3 (n={n}, f={f})")
    alive = list(range(n))
    w_ext = np.zeros((theta, n), np.float32)
    w_agr = np.zeros((theta, n), np.float32)
    for r in range(theta):
        m = len(alive) - f - 2
        scores = {i: float(np.sum(np.sort(
            [dists[i, j] for j in alive if j != i])[:m])) for i in alive}
        order = sorted(alive, key=lambda i: (scores[i], i))
        w_ext[r, order[0]] = 1.0
        w_agr[r, order[:m]] = np.float32(1.0) / np.float32(m)
        alive.remove(order[0])
    return w_ext, w_agr, beta


def coordinate_phase(g_ext, g_agr, beta: int):
    """Per coordinate: the median of ``g_ext``'s rows, then the mean of
    the ``beta`` rows of ``g_agr`` closest to it (ties by row), summed in
    row order.  Also returns each coordinate's margin: how much farther
    the next row lies than the last one taken."""
    theta = g_ext.shape[0]
    s = jnp.sort(g_ext, axis=0)
    med = s[theta // 2] if theta % 2 else 0.5 * (s[theta // 2 - 1]
                                                + s[theta // 2])
    dist = jnp.abs(g_agr - med[None])
    rank = jnp.argsort(jnp.argsort(dist, axis=0, stable=True), axis=0)
    acc = jnp.zeros(g_agr.shape[1:], jnp.float32)
    for i in range(theta):
        acc = acc + jnp.where(rank[i] < beta, g_agr[i], 0.0)
    sd = jnp.sort(dist, axis=0)
    margin = sd[beta] - sd[beta - 1] if beta < theta else \
        jnp.full(acc.shape, jnp.inf, jnp.float32)
    return acc / float(beta), margin


@functools.lru_cache(maxsize=None)
def _apply(precision, beta: int):
    def run(w_ext, w_agr, x):
        g_ext = tensordot(w_ext, x, (1, 0), precision)
        g_agr = tensordot(w_agr, x, (1, 0), precision)
        return coordinate_phase(g_ext, g_agr, beta)
    return jax.jit(run)


def apply(w_ext, w_agr, beta: int, x, precision):
    """The plan applied to ``x`` (n, ...): ``(aggregate, margin)``."""
    return _apply(precision, beta)(jnp.asarray(w_ext), jnp.asarray(w_agr), x)


def aggregate_tree(tree, f: int, precision):
    """The whole tree's aggregate, leaf by leaf."""
    leaves, treedef = jax.tree.flatten(tree)
    w_ext, w_agr, beta = plan(distances(leaves, precision), f)
    out = [apply(w_ext, w_agr, beta, x, precision)[0] for x in leaves]
    return jax.tree.unflatten(treedef, out)


def sample_index(shape: tuple, k: int, rng: np.random.Generator) -> tuple:
    """Up to ``k`` distinct coordinates of a leaf of ``shape`` (without
    its worker axis), as an index tuple; every coordinate when fewer."""
    numel = math.prod(shape)
    if numel <= k:
        flat = np.arange(numel)
    elif numel <= 4 * k:
        flat = np.sort(rng.choice(numel, size=k, replace=False))
    else:
        # 2k draws from more than 4k values keep at least 1.5k distinct
        drawn = np.unique(rng.integers(0, numel, size=2 * k))
        flat = np.sort(rng.choice(drawn, size=k, replace=False))
    return np.unravel_index(flat, shape)
