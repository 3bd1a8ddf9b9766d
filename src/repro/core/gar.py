"""Gradient Aggregation Rules (GARs) — the paper's contribution.

All rules take a stacked gradient matrix ``G`` of shape ``(n, d)`` (n workers,
d coordinates) and return the aggregated gradient ``(d,)``.  Everything is
jit-safe (static shapes, masked ``lax`` control flow) and coordinate-sharded:
under ``pjit`` the ``d`` axis can live on the ``model`` mesh axis; the only
cross-shard reduction is the pairwise-distance accumulation (see DESIGN.md §3).

Implemented rules
-----------------
* ``average``            — the non-robust optimum (paper's baseline).
* ``coordinate_median``  — MEDIAN baseline from §V.
* ``trimmed_mean``       — classic robust baseline (Yin et al. 2018).
* ``krum``               — Blanchard et al. 2017 (m = 1).
* ``multi_krum``         — paper §III: average of the m = n-f-2 best-scored.
* ``bulyan``             — El-Mhamdi et al. 2018, on top of iterated Krum.
* ``multi_bulyan``       — paper §IV / Algorithm 1: Bulyan over MULTI-KRUM.

The Multi-Bulyan extraction loop follows Algorithm 1 exactly: θ = n-2f-2
rounds; round r runs MULTI-KRUM over the remaining pool of k = n-r gradients
with m_r = k-f-2, records the single *winner* (extracted from the pool) into
``G_ext`` and the m_r-average into ``G_agr``; the coordinate phase takes the
median of ``G_ext`` and averages, per coordinate, the β = θ-2f values of
``G_agr`` closest to that median.

The sequential pool removal of Algorithm 1 is re-expressed as a masked
``lax.fori_loop`` (dead entries get +inf distance/score) so shapes stay
static under jit; equivalence with a literal sequential-removal reference is
property-tested in ``tests/test_gar_semantics.py``.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

Array = jax.Array

_INF = jnp.inf


# --------------------------------------------------------------------------
# differentiable ordering helpers
#
# This jax build's sort JVP is broken (GatherDimensionNumbers
# operand_batching_dims TypeError), so every sort/median on a differentiable
# value goes through argsort-on-stopped-keys + take_along_axis: the ordering
# is piecewise-constant in the inputs anyway, and the gather VJP is intact.
# --------------------------------------------------------------------------
def _sort_by_value(x: Array, axis: int = 0) -> Array:
    idx = jnp.argsort(jax.lax.stop_gradient(x), axis=axis)
    return jnp.take_along_axis(x, idx, axis=axis)


def _median_axis0(x: Array) -> Array:
    s = _sort_by_value(x, axis=0)
    n = x.shape[0]
    if n % 2:
        return s[n // 2]
    return 0.5 * (s[n // 2 - 1] + s[n // 2])


# --------------------------------------------------------------------------
# distances & scores
# --------------------------------------------------------------------------
def pairwise_sqdist(G: Array, *, precision=jax.lax.Precision.HIGHEST) -> Array:
    """(n, d) -> (n, n) matrix of squared euclidean distances.

    Uses the gram-matrix decomposition ``||a-b||² = ||a||² + ||b||² - 2 a·b``
    so the O(n²d) inner product rides the MXU.  fp32 accumulation.
    ``kernels/pairwise_sqdist.py`` is the Pallas version of this exact
    contraction; this is the XLA/ref path.
    """
    Gf = G.astype(jnp.float32)
    sq = jnp.sum(Gf * Gf, axis=-1)                       # (n,)
    gram = jnp.matmul(Gf, Gf.T, precision=precision)     # (n, n)
    d2 = sq[:, None] + sq[None, :] - 2.0 * gram
    # numerical floor: distances are nonnegative; zero the diagonal exactly.
    d2 = jnp.maximum(d2, 0.0)
    n = G.shape[0]
    return d2 * (1.0 - jnp.eye(n, dtype=d2.dtype))


def krum_scores(dists: Array, f: int, alive: Optional[Array] = None,
                n_neighbors: Optional[Array] = None) -> Array:
    """Krum score per worker: sum of sq-distances to its nearest neighbours.

    ``dists``: (n, n) pairwise squared distances.
    ``alive``: optional (n,) bool mask of pool membership (dead workers are
    excluded both as scorers and as neighbour candidates).
    ``n_neighbors``: number of neighbours (k - f - 2 where k = pool size);
    may be a traced scalar — the sum-of-smallest is computed with a sorted
    prefix mask so it does not need to be static.
    """
    n = dists.shape[0]
    if alive is None:
        alive = jnp.ones((n,), dtype=bool)
    k_pool = jnp.sum(alive.astype(jnp.int32))
    if n_neighbors is None:
        n_neighbors = k_pool - f - 2
    eye = jnp.eye(n, dtype=bool)
    valid = alive[None, :] & ~eye                      # candidate neighbours of i
    masked = jnp.where(valid, jax.lax.stop_gradient(dists), _INF)
    srt = jnp.sort(masked, axis=1)                     # (n, n) ascending
    take = jnp.arange(n)[None, :] < n_neighbors        # first n_neighbors cols
    scores = jnp.sum(jnp.where(take, srt, 0.0), axis=1)
    return jnp.where(alive, scores, _INF)


def _select_smallest_mask(scores: Array, m) -> Array:
    """Boolean mask of the m smallest-score entries (ties broken by index).

    ``m`` may be traced.  Implemented by rank comparison: rank(i) = number of
    entries strictly smaller, plus number of equal entries with smaller index.
    """
    n = scores.shape[0]
    idx = jnp.arange(n)
    lt = scores[None, :] < scores[:, None]
    eq = (scores[None, :] == scores[:, None]) & (idx[None, :] < idx[:, None])
    rank = jnp.sum(lt | eq, axis=1)
    return rank < m


# --------------------------------------------------------------------------
# baselines
# --------------------------------------------------------------------------
def average(G: Array, f: int = 0) -> Array:
    """Plain averaging — the fastest but non-byzantine-resilient rule."""
    del f
    return jnp.mean(G, axis=0)


def coordinate_median(G: Array, f: int = 0) -> Array:
    """Coordinate-wise median (the MEDIAN baseline of §V)."""
    del f
    return _median_axis0(G)


def trimmed_mean(G: Array, f: int) -> Array:
    """Coordinate-wise trimmed mean: drop the f largest and f smallest."""
    n = G.shape[0]
    if n <= 2 * f:
        raise ValueError(f"trimmed_mean needs n > 2f (n={n}, f={f})")
    srt = _sort_by_value(G, axis=0)
    return jnp.mean(srt[f:n - f], axis=0)


# --------------------------------------------------------------------------
# Krum family
# --------------------------------------------------------------------------
def multi_krum_mask(G: Array, f: int, m: Optional[int] = None,
                    dists: Optional[Array] = None) -> Tuple[Array, Array]:
    """Return (selection mask (n,), scores (n,)) of MULTI-KRUM.

    m defaults to the paper's m̃ = n - f - 2.
    """
    n = G.shape[0]
    if n < 2 * f + 3:
        raise ValueError(f"multi-krum needs n >= 2f+3 (n={n}, f={f})")
    if m is None:
        m = n - f - 2
    if dists is None:
        dists = pairwise_sqdist(G)
    # selection is piecewise-constant in G: the aggregate's gradient flows
    # through the selected average only, never through the plan
    scores = jax.lax.stop_gradient(krum_scores(dists, f))
    return _select_smallest_mask(scores, m), scores


def krum(G: Array, f: int, dists: Optional[Array] = None) -> Array:
    """Krum: the single gradient with the smallest score."""
    mask, _ = multi_krum_mask(G, f, m=1, dists=dists)
    w = mask.astype(G.dtype)
    return (w @ G) / jnp.sum(w)


def multi_krum(G: Array, f: int, m: Optional[int] = None,
               dists: Optional[Array] = None) -> Array:
    """MULTI-KRUM: average of the m best-scored gradients (§III)."""
    mask, _ = multi_krum_mask(G, f, m=m, dists=dists)
    w = mask.astype(jnp.float32)
    return ((w @ G.astype(jnp.float32)) / jnp.sum(w)).astype(G.dtype)


# --------------------------------------------------------------------------
# Bulyan family
# --------------------------------------------------------------------------
def extraction_plan(dists: Array, f: int, theta: int,
                    multi: bool = True) -> Tuple[Array, Array]:
    """θ rounds of (MULTI-)KRUM extraction, in *score space only*.

    The plan depends only on the (n, n) distance matrix — an O(n²·θ·log n)
    scalar computation, replicated on every shard.  Applying the plan to the
    actual gradients is then a pair of tiny einsums per leaf, which is what
    lets the whole Bulyan pipeline shard over the model axis (DESIGN.md §3).

    Returns ``(ext_weights, agr_weights)``, each ``(theta, n)`` row-stochastic:
    * ``ext_weights[r]`` — one-hot row selecting the round-r winner
      (Algorithm 1 line 19, first output);
    * ``agr_weights[r]`` — uniform weights over the round-r MULTI-KRUM
      selection of size m_r = (n-r)-f-2 if ``multi``, else the winner one-hot
      (classic BULYAN).
    """
    n = dists.shape[0]

    def round_fn(r, carry):
        alive, w_ext, w_agr = carry
        k_pool = n - r
        m_r = k_pool - f - 2
        scores = krum_scores(dists, f, alive=alive, n_neighbors=m_r)
        winner = jnp.argmin(scores)
        one_hot = jnp.zeros((n,), jnp.float32).at[winner].set(1.0)
        if multi:
            sel = _select_smallest_mask(scores, m_r).astype(jnp.float32)
            agr = sel / jnp.maximum(jnp.sum(sel), 1.0)
        else:
            agr = one_hot
        w_ext = w_ext.at[r].set(one_hot)
        w_agr = w_agr.at[r].set(agr)
        alive = alive.at[winner].set(False)
        return alive, w_ext, w_agr

    alive0 = jnp.ones((n,), dtype=bool)
    z = jnp.zeros((theta, n), dtype=jnp.float32)
    dists = jax.lax.stop_gradient(dists)   # plan is not differentiated
    _, w_ext, w_agr = jax.lax.fori_loop(0, theta, round_fn, (alive0, z, z))
    return jax.lax.stop_gradient(w_ext), jax.lax.stop_gradient(w_agr)


def _extraction_rounds(G: Array, f: int, theta: int,
                       dists: Optional[Array] = None,
                       multi: bool = True) -> Tuple[Array, Array]:
    """Apply the extraction plan to an (n, d) stack -> (G_ext, G_agr)."""
    dists = pairwise_sqdist(G) if dists is None else dists
    w_ext, w_agr = extraction_plan(dists, f, theta, multi=multi)
    Gf = G.astype(jnp.float32)
    return w_ext @ Gf, w_agr @ Gf


def bulyan_coordinate_phase(G_ext: Array, G_agr: Array, beta: int) -> Array:
    """BULYAN's coordinate phase (Algorithm 1 lines 21-24).

    Per coordinate j: median M[j] of ``G_ext[:, j]``; average the β entries of
    ``G_agr[:, j]`` closest to M[j].  Purely coordinate-local → shards freely
    over the model axis.  ``kernels/coord_select.py`` is the Pallas version.
    """
    med = _median_axis0(G_ext)
    dist = jax.lax.stop_gradient(jnp.abs(G_agr - med[None]))  # (theta, ...)
    order = jnp.argsort(dist, axis=0)                   # (theta, ...)
    ranks = jnp.argsort(order, axis=0)                  # rank of each entry
    return _masked_row_mean(ranks < beta, G_agr, beta)


def _masked_row_mean(sel: Array, x: Array, beta: int) -> Array:
    """Sum of the ``sel``-ed rows of ``x`` over β, added row 0, row 1, …
    in that order.  A spelled-out order (not ``jnp.sum``): a backend may
    reduce an axis in any association, and the Pallas kernels
    (``kernels/coord_select.py``) add in this order too."""
    acc = jnp.where(sel[0], x[0], 0.0)
    for i in range(1, x.shape[0]):
        acc = acc + jnp.where(sel[i], x[i], 0.0)
    return acc / float(beta)


def _bulyan_family(G: Array, f: int, *, multi: bool,
                   dists: Optional[Array] = None) -> Array:
    n = G.shape[0]
    if n < 4 * f + 3:
        raise ValueError(f"bulyan needs n >= 4f+3 (n={n}, f={f})")
    theta = n - 2 * f - 2
    beta = theta - 2 * f
    g_ext, g_agr = _extraction_rounds(G, f, theta, dists=dists, multi=multi)
    out = bulyan_coordinate_phase(g_ext, g_agr, beta)
    return out.astype(G.dtype)


def bulyan(G: Array, f: int, dists: Optional[Array] = None) -> Array:
    """Classic BULYAN: iterated Krum extraction + coordinate phase."""
    return _bulyan_family(G, f, multi=False, dists=dists)


def multi_bulyan(G: Array, f: int, dists: Optional[Array] = None) -> Array:
    """MULTI-BULYAN (Algorithm 1): BULYAN over MULTI-KRUM aggregates."""
    return _bulyan_family(G, f, multi=True, dists=dists)


# --------------------------------------------------------------------------
# legacy registry (deprecation shims over repro.core.api)
#
# The raw rule functions above stay as the numerical primitives (and the
# reference surface for tests/test_gar_semantics.py); dispatch-by-name now
# lives in the plan/apply Aggregator registry in ``core/api.py``.  GARS and
# ``aggregate`` are kept so old call sites keep working — ``aggregate``
# routes through the registry and is bitwise-identical to it.
# --------------------------------------------------------------------------
GARS: dict[str, Callable[..., Array]] = {
    "average": average,
    "median": coordinate_median,
    "trimmed_mean": trimmed_mean,
    "krum": krum,
    "multi_krum": multi_krum,
    "bulyan": bulyan,
    "multi_bulyan": multi_bulyan,
}


def get_gar(name: str) -> Callable[..., Array]:
    try:
        return GARS[name]
    except KeyError:
        raise KeyError(f"unknown GAR {name!r}; available: {sorted(GARS)}") from None


def aggregate(G: Array, f: int, name: str = "multi_bulyan") -> Array:
    """Aggregate an (n, d) gradient stack with the named rule.

    .. deprecated:: use :func:`repro.core.api.aggregate_matrix` / the
       Aggregator registry (this shim delegates to it).
    """
    from repro.core import api  # local import: api imports this module
    return api.aggregate_matrix(G, f, name)
