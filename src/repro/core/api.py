"""Plan/apply aggregation API — the public seam of the whole system.

The paper's O(d) claim for multi-Bulyan rests on a structural split that this
module promotes to the public API (DESIGN.md §3):

* ``plan(stats)``  — runs on the replicated ``(n, n)`` squared-distance
  matrix / per-worker norms only.  O(n²·θ·log n) scalar work, no touch of
  the d axis, returns *static-shape* weight matrices.
* ``apply(plan, grads)`` — sharding-preserving per-leaf einsums plus the
  purely coordinate-local phase over the d axis.  No communication on the
  model axis.

Every GAR is an :class:`Aggregator` registered via :func:`register_gar` with
capability flags (``needs_dists``, ``coordinate_local``, ``min_n``).  The
legacy entry points ``core.gar.aggregate`` and ``core.robust.tree_aggregate``
are thin shims over this registry (bitwise-identical outputs — tested in
``tests/test_agg_api.py``).

A composable pre-aggregation :class:`Transform` stage runs on the stacked
gradients *before* the GAR sees them — worker momentum (Farhadkhani et al.
2022), per-worker clipping, nearest-neighbour mixing.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import gar as G
from repro.obs.trace import scope

Array = jax.Array
PyTree = Any


# ==========================================================================
# statistics (the plan's only input)
# ==========================================================================
@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=("dists", "sq_norms"),
    meta_fields=("n", "f"))
@dataclasses.dataclass(frozen=True)
class AggStats:
    """Replicated per-round statistics the selection plan is computed from.

    ``dists`` is the global (n, n) squared-distance matrix (fp32), present
    only when the rule's ``needs_dists`` flag is set; ``sq_norms`` the per
    worker squared l2 norms.  Both are O(n²) scalars — tiny next to d.
    """

    n: int
    f: int
    dists: Optional[Array] = None
    sq_norms: Optional[Array] = None


def _leaf_stats_contrib(leaf: Array) -> Tuple[Array, Array]:
    """One leaf's raw (dists, sq_norms) contribution — the XLA formula.

    Contraction over all parameter dims: sharded dims reduce locally + one
    psum under GSPMD.  HIGHEST: distances between near-identical honest
    gradients must not lose bits to bf16-pass matmuls on TPU — score order
    decides selection.  The single shared implementation keeps the
    streaming pass-1 path (leaf_sqdist_contrib) and the stacked path
    (tree_pairwise_stats) on the exact same float summation.
    """
    x = leaf.astype(jnp.float32)
    axes = _param_axes(x)
    sq = jnp.sum(x * x, axis=axes)
    gram = jax.lax.dot_general(
        x, x, ((axes, axes), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32) if x.ndim == 2 else \
        jnp.tensordot(x, x, axes=(axes, axes),
                      precision=jax.lax.Precision.HIGHEST)
    return sq[:, None] + sq[None, :] - 2.0 * gram, sq


def leaf_sqdist_contrib(leaf: Array, *, use_pallas: bool = False) -> Array:
    """One leaf's raw contribution to the global (n, n) distance matrix.

    Raw (unclamped, diagonal kept) so cross-leaf accumulation stays a plain
    sum; callers finalise with :func:`finalize_dists`.
    """
    if use_pallas:
        from repro.kernels import ops as kops
        # raw contribution, matching this function's contract — streaming
        # pass 1 accumulates the exact float sum the stacked path's
        # tree_pairwise_stats produces.  The kernel still writes its (1, n)
        # norm output (pallas_call is opaque to XLA DCE); that extra VMEM
        # write is noise next to the tile loads.
        return kops.pairwise_stats(_leaf2d(leaf))[0]
    return _leaf_stats_contrib(leaf)[0]


def finalize_dists(total: Array) -> Array:
    """Numerical floor + exact-zero diagonal on an accumulated (n, n) sum."""
    total = jnp.maximum(total, 0.0)
    n = total.shape[0]
    return total * (1.0 - jnp.eye(n, dtype=total.dtype))


def tree_pairwise_sqdist(grads: PyTree, *, use_pallas: bool = False) -> Array:
    """Sum of per-leaf pairwise squared distances -> global (n, n) matrix."""
    return tree_pairwise_stats(grads, use_pallas=use_pallas)[0]


def tree_pairwise_stats(grads: PyTree, *, use_pallas: bool = False
                        ) -> Tuple[Array, Array]:
    """Single pass over the stack: (global (n, n) sq-dists, (n,) sq-norms).

    On the Pallas path every leaf is read from HBM exactly once — the
    ``pairwise_stats`` kernel emits that leaf's raw distance contribution
    and its norm contribution from the same VMEM tile load; both are
    accumulated across leaves and the distances finalised once.  The XLA
    path shares the gram intermediate so the norms also cost no extra read.
    """
    total_d, total_s = raw_pairwise_stats(grads, use_pallas=use_pallas)
    return finalize_dists(total_d), total_s


def tree_sq_norms(grads: PyTree) -> Array:
    """Per-worker squared l2 norms across every leaf -> (n,) fp32."""
    leaves = jax.tree.leaves(grads)
    n = leaves[0].shape[0]
    total = jnp.zeros((n,), dtype=jnp.float32)
    for leaf in leaves:
        x = leaf.astype(jnp.float32)
        total = total + jnp.sum(x * x, axis=_param_axes(x))
    return total


def _as_encoded(grads: PyTree):
    """The wire container, or None for a plain pytree.

    Cheap duck check first so the common path never imports ``repro.comm``
    (``core`` stays the bottom layer; the comm subsystem imports only
    ``core.attacks``, so the lazy import is cycle-free).
    """
    if type(grads).__name__ != "EncodedGrads":
        return None
    from repro.comm import codecs as CC
    return grads if CC.is_encoded(grads) else None


def compute_stats(grads: PyTree, f: int, *, needs_dists: bool = True,
                  needs_norms: bool = False, use_pallas: bool = False,
                  dists: Optional[Array] = None,
                  mesh_ctx: Optional["MeshContext"] = None) -> AggStats:
    """Build the :class:`AggStats` a rule's ``plan`` consumes.

    Only what the capability flags ask for is computed — ``average`` pays
    zero extra collectives, distance rules pay the one (n, n) all-reduce.
    When distances are needed the single-pass kernel also yields the norms
    as a free byproduct of the same HBM read, so ``sq_norms`` is populated
    whenever ``dists`` is computed here.

    ``grads`` may be a ``repro.comm`` :class:`EncodedGrads` wire container:
    statistics then run straight on the quantized payloads — through the
    fused dequantize→stats kernel under ``use_pallas`` (DESIGN.md §9) —
    without materialising the decoded stack here.

    With ``mesh_ctx`` the statistics run mesh-native (DESIGN.md §10): the
    worker axis is sharded over ``mesh_ctx.worker_axes`` inside a
    ``shard_map`` and every device computes only its row block of the
    (n, n) matrix — bitwise-identical to the replicated path under
    ``use_pallas``; see :func:`sharded_raw_stats` for the XLA substrate.

    Everything here traces under the ``robust.stats`` scope.
    """
    with scope("stats"):
        enc = _as_encoded(grads)
        if enc is not None:
            def enc_stats():
                if mesh_ctx is not None:
                    raw, sq = sharded_raw_stats(enc, mesh_ctx=mesh_ctx,
                                                use_pallas=use_pallas)
                    return finalize_dists(raw), sq
                from repro.comm import codecs as CC
                return CC.encoded_pairwise_stats(enc, use_pallas=use_pallas)

            norms = None
            if needs_dists and dists is None:
                dists, norms = enc_stats()
            if needs_norms and norms is None:
                norms = enc_stats()[1]
            return AggStats(n=enc.n, f=f, dists=dists, sq_norms=norms)
        leaves = jax.tree.leaves(grads)
        if not leaves:
            raise ValueError("empty gradient pytree")
        n = leaves[0].shape[0]
        for leaf in leaves:
            if leaf.shape[0] != n:
                raise ValueError(
                    "all leaves must share the worker axis size")
        norms = None
        if needs_dists and dists is None:
            if mesh_ctx is not None:
                raw, norms = sharded_raw_stats(grads, mesh_ctx=mesh_ctx,
                                               use_pallas=use_pallas)
                dists = finalize_dists(raw)
            else:
                dists, norms = tree_pairwise_stats(grads,
                                                   use_pallas=use_pallas)
        if needs_norms and norms is None:
            # norms alone are O(n·d) row sums — replicated compute is
            # cheaper than the sharded distance phase even on a mesh, and
            # the values are identical (same per-leaf accumulation order)
            norms = tree_sq_norms(grads)
        return AggStats(n=n, f=f, dists=dists, sq_norms=norms)


# ==========================================================================
# mesh-native (SPMD) execution — DESIGN.md §10
# ==========================================================================
@dataclasses.dataclass(frozen=True)
class MeshContext:
    """Execution context for the mesh-native (shard_map) aggregation path.

    ``worker_axes`` name the mesh axes carrying the byzantine worker
    dimension (``("pod", "data")`` multi-pod, ``("data",)`` single-pod);
    ``model_axis`` the tensor-parallel axis the apply phase shards the
    d dimension over (``None`` disables d-sharding).  The context is pure
    metadata — hashable, jit-static — so step builders can close over it.
    """

    mesh: Any
    worker_axes: Tuple[str, ...] = ("data",)
    model_axis: Optional[str] = "model"

    @classmethod
    def for_mesh(cls, mesh, worker_axes: Optional[Sequence[str]] = None
                 ) -> "MeshContext":
        """Derive the canonical context from a mesh's axis names."""
        names = tuple(mesh.axis_names)
        if worker_axes is None:
            worker_axes = ("pod", "data") if "pod" in names else ("data",)
        missing = [a for a in worker_axes if a not in names]
        if missing:
            raise ValueError(
                f"worker axes {missing} not in mesh axes {names}")
        return cls(mesh=mesh, worker_axes=tuple(worker_axes),
                   model_axis="model" if "model" in names else None)

    @property
    def worker_size(self) -> int:
        sizes = dict(self.mesh.shape)
        out = 1
        for a in self.worker_axes:
            out *= sizes[a]
        return out

    @property
    def model_size(self) -> int:
        return dict(self.mesh.shape)[self.model_axis] \
            if self.model_axis is not None else 1

    @property
    def worker_entry(self):
        """The PartitionSpec entry for the worker axis (str or tuple)."""
        return self.worker_axes if len(self.worker_axes) > 1 \
            else self.worker_axes[0]


def _shard_map(fn, ctx: MeshContext, in_specs, out_specs):
    return jax.shard_map(fn, mesh=ctx.mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _worker_index(ctx: MeshContext) -> Array:
    """Flat index of this device's worker-axis shard (inside shard_map)."""
    idx = jnp.zeros((), jnp.int32)
    sizes = dict(ctx.mesh.shape)
    for a in ctx.worker_axes:
        idx = idx * sizes[a] + jax.lax.axis_index(a)
    return idx


def _pad_rows(x: Array, n_pad: int) -> Array:
    return jnp.pad(x, ((0, n_pad - x.shape[0]),) + ((0, 0),) * (x.ndim - 1))


def _block_stats_contrib(x_loc: Array, x_full: Array
                         ) -> Tuple[Array, Array]:
    """Row-block partial of :func:`_leaf_stats_contrib`.

    ``x_loc`` is this device's worker rows, ``x_full`` the gathered stack.
    Each output element is the same full-d reduction the replicated formula
    computes.  On the CPU the block is bitwise-identical to the matching
    rows of ``_leaf_stats_contrib(x_full)`` (tests/test_spmd.py); on a TPU
    XLA tiles the (n/W, n) and (n, n) grams differently, so the two agree
    to f32 rounding only.
    """
    xl = x_loc.astype(jnp.float32)
    xf = x_full.astype(jnp.float32)
    axes = _param_axes(xf)
    sq_full = jnp.sum(xf * xf, axis=axes)
    sq_loc = jnp.sum(xl * xl, axis=axes)
    gram = jax.lax.dot_general(
        xl, xf, ((axes, axes), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32) if xf.ndim == 2 else \
        jnp.tensordot(xl, xf, axes=(axes, axes),
                      precision=jax.lax.Precision.HIGHEST)
    return sq_loc[:, None] + sq_full[None, :] - 2.0 * gram, sq_full


def sharded_raw_stats(grads: PyTree, *, mesh_ctx: MeshContext,
                      use_pallas: bool = False) -> Tuple[Array, Array]:
    """Mesh-native single pass: (raw (n, n) sq-dists, (n,) sq-norms).

    The worker axis of every leaf (gradient rows, or ``EncodedGrads``
    payload/sidecar rows) is sharded over ``mesh_ctx.worker_axes`` inside a
    ``shard_map``; each device all-gathers the rows of one leaf at a time,
    computes its *row block* of that leaf's contribution — the O(n²·d)
    distance phase decomposes across the worker shards, the paper's §IV
    parallelisation claim — and the blocks are reassembled by the out-spec.
    Raw contract matches :func:`leaf_sqdist_contrib` (no clamp, diagonal
    kept).  Under ``use_pallas`` the kernels fix the float summation order,
    so results are bitwise-identical to the replicated path on any backend;
    the XLA substrate is bitwise on the CPU (tests/test_spmd.py) and within
    f32 rounding on a TPU (:func:`_block_stats_contrib`).

    n not divisible by the worker-shard count is zero-row padded; padded
    rows decode/contract to exact zeros and are sliced away.  Under
    ``use_pallas`` each device runs the *rectangular* stats kernels
    (``pairwise_stats_rect`` / ``dequant_stats_rect``) — its own row block
    against the gathered stack, O(n_loc·n·d) instead of the square
    kernel's redundant O(n²·d) per device — bitwise-identical to the
    square kernels' matching rows at the shared autotuned ``d_tile``
    (kernels/pairwise_sqdist.py header), same wire cost.
    """
    enc = _as_encoded(grads)
    W = mesh_ctx.worker_size
    lead = mesh_ctx.worker_entry
    axes_names = mesh_ctx.worker_axes

    if enc is not None:
        from repro.comm import codecs as CC
        codec = CC.get_codec(enc.spec)
        n = enc.n
        n_pad = -(-n // W) * W
        n_loc = n_pad // W
        p_leaves = jax.tree.leaves(enc.payload)
        s_leaves = jax.tree.leaves(enc.sidecar) \
            if enc.sidecar is not None else [None] * len(p_leaves)
        shapes = [(n_pad,) + tuple(s[1:]) for s in enc.shapes]
        operands = [_pad_rows(x, n_pad) for x in p_leaves] + \
            [_pad_rows(s, n_pad) for s in s_leaves if s is not None]
        has_sidecar = [s is not None for s in s_leaves]
        in_specs = tuple(P(*((lead,) + (None,) * (x.ndim - 1)))
                         for x in operands)

        def local(*flat):
            ps = flat[: len(p_leaves)]
            ss_iter = iter(flat[len(p_leaves):])
            idx = _worker_index(mesh_ctx)
            total_d = jnp.zeros((n_loc, n_pad), jnp.float32)
            total_s = jnp.zeros((n_pad,), jnp.float32)
            for p_loc, has_s, shape in zip(ps, has_sidecar, shapes):
                s_loc = next(ss_iter) if has_s else None
                p_full = jax.lax.all_gather(p_loc, axes_names, axis=0,
                                            tiled=True)
                s_full = None if s_loc is None else \
                    jax.lax.all_gather(s_loc, axes_names, axis=0, tiled=True)
                if use_pallas:
                    dd, sq = CC.encoded_leaf_block_contrib(
                        codec, p_loc, s_loc, p_full, s_full, shape,
                        row_start=idx * n_loc, n_loc=n_loc)
                else:
                    g_full = codec.decode_leaf(
                        _leaf2d(p_full), s_full, shape).reshape(shape)
                    g_loc = jax.lax.dynamic_slice_in_dim(
                        g_full, idx * n_loc, n_loc, 0)
                    dd, sq = _block_stats_contrib(g_loc, g_full)
                total_d = total_d + dd
                total_s = total_s + sq
            return total_d, total_s

        fn = _shard_map(local, mesh_ctx, in_specs,
                        (P(lead, None), P(None)))
        dd, sq = fn(*operands)
        return dd[:n, :n], sq[:n]

    leaves = jax.tree.leaves(grads)
    if not leaves:
        raise ValueError("empty gradient pytree")
    n = leaves[0].shape[0]
    n_pad = -(-n // W) * W
    n_loc = n_pad // W
    padded = [_pad_rows(x, n_pad) for x in leaves]
    in_specs = tuple(P(*((lead,) + (None,) * (x.ndim - 1))) for x in padded)

    def local(*loc_leaves):
        total_d = jnp.zeros((n_loc, n_pad), jnp.float32)
        total_s = jnp.zeros((n_pad,), jnp.float32)
        for xl in loc_leaves:
            full = jax.lax.all_gather(xl, axes_names, axis=0, tiled=True)
            if use_pallas:
                from repro.kernels import ops as kops
                dd, sq = kops.pairwise_stats_rect(_leaf2d(xl),
                                                  _leaf2d(full))
            else:
                dd, sq = _block_stats_contrib(xl, full)
            total_d = total_d + dd
            total_s = total_s + sq
        return total_d, total_s

    fn = _shard_map(local, mesh_ctx, in_specs, (P(lead, None), P(None)))
    dd, sq = fn(*padded)
    return dd[:n, :n], sq[:n]


def sharded_raw_stats_model_axis(grads: PyTree, *, mesh_ctx: MeshContext,
                                 use_pallas: bool = False
                                 ) -> Tuple[Array, Array]:
    """Model-axis-sharded single pass: raw ((n, n) sq-dists, (n,) norms)
    from (n/W, d/M) leaf tiles — the §10 tensor-parallel stats seam.

    Where :func:`sharded_raw_stats` keeps every leaf's d axis replicated,
    this variant shards it over ``mesh_ctx.model_axis`` as well: each
    device all-gathers only its *column shard*'s worker rows, runs the
    rectangular stats kernel on the (n_loc, d/M) × (n, d/M) tile pair,
    and the per-shard partial contributions ``psum`` over the model axis.
    No replicated-leaf round-trip: a tensor-parallel trainer can feed its
    grads without first all-gathering d.

    Float caveat: the model-axis ``psum`` is a different summation order
    than the replicated full-d contraction, so parity with the replicated
    path is bitwise at M = 1 (plain CI) and ~1e-6 at M > 1 — unlike the
    worker-axis sharding, which is bitwise at any W on the CPU and, with
    the kernels, on a TPU.  Leaf columns pad to
    a multiple of M with exact zeros.
    """
    leaves = jax.tree.leaves(grads)
    if not leaves:
        raise ValueError("empty gradient pytree")
    n = leaves[0].shape[0]
    W = mesh_ctx.worker_size
    M = mesh_ctx.model_size
    lead = mesh_ctx.worker_entry
    axes_names = mesh_ctx.worker_axes
    n_pad = -(-n // W) * W
    n_loc = n_pad // W
    flat = []
    for x in leaves:
        x2 = _leaf2d(x)
        m_pad = (-x2.shape[1]) % M
        if m_pad:
            x2 = jnp.pad(x2, ((0, 0), (0, m_pad)))
        flat.append(_pad_rows(x2, n_pad))
    in_specs = tuple(P(lead, mesh_ctx.model_axis) for _ in flat)

    def local(*loc_leaves):
        total_d = jnp.zeros((n_loc, n_pad), jnp.float32)
        total_s = jnp.zeros((n_pad,), jnp.float32)
        for xl in loc_leaves:
            full = jax.lax.all_gather(xl, axes_names, axis=0, tiled=True)
            if use_pallas:
                from repro.kernels import ops as kops
                dd, sq = kops.pairwise_stats_rect(xl, full)
            else:
                dd, sq = _block_stats_contrib(xl, full)
            total_d = total_d + dd
            total_s = total_s + sq
        if mesh_ctx.model_axis is not None:
            total_d = jax.lax.psum(total_d, mesh_ctx.model_axis)
            total_s = jax.lax.psum(total_s, mesh_ctx.model_axis)
        return total_d, total_s

    fn = _shard_map(local, mesh_ctx, in_specs, (P(lead, None), P(None)))
    dd, sq = fn(*flat)
    return dd[:n, :n], sq[:n]


def raw_pairwise_stats(grads: PyTree, *, use_pallas: bool = False,
                       mesh_ctx: Optional[MeshContext] = None
                       ) -> Tuple[Array, Array]:
    """Raw accumulation unit shared by stacked and streaming trainers.

    (raw (n, n) sq-dists, (n,) sq-norms) of a stacked pytree *or* an
    ``EncodedGrads`` container — unclamped, diagonal kept; finalise once
    with :func:`finalize_dists`.  Bit-exact parity with the stacked
    single pass requires matching its flat per-leaf accumulation order:
    a cross-block accumulator must add one *leaf* at a time (as the
    streaming pass-1 does), not pre-summed per-block subtotals, or the
    float sums reassociate.  Routes through :func:`sharded_raw_stats`
    when a :class:`MeshContext` is given.
    """
    if mesh_ctx is not None:
        return sharded_raw_stats(grads, mesh_ctx=mesh_ctx,
                                 use_pallas=use_pallas)
    enc = _as_encoded(grads)
    if enc is not None:
        from repro.comm import codecs as CC
        return CC.encoded_raw_stats(enc, use_pallas=use_pallas)
    leaves = jax.tree.leaves(grads)
    if not leaves:
        raise ValueError("empty gradient pytree")
    n = leaves[0].shape[0]
    total_d = jnp.zeros((n, n), jnp.float32)
    total_s = jnp.zeros((n,), jnp.float32)
    for leaf in leaves:
        if use_pallas:
            from repro.kernels import ops as kops
            dd, sq = kops.pairwise_stats(_leaf2d(leaf))
        else:
            dd, sq = _leaf_stats_contrib(leaf)
        total_d = total_d + dd
        total_s = total_s + sq
    return total_d, total_s


# ==========================================================================
# plans
# ==========================================================================
@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=("weights", "w_ext", "w_agr"),
    meta_fields=("kind", "n", "f", "beta"))
@dataclasses.dataclass(frozen=True)
class AggPlan:
    """Static-shape output of a rule's selection phase.

    ``kind`` picks the apply path:
    * ``"mean"``       — plain per-leaf mean over the worker axis;
    * ``"weighted"``   — one (n,) convex weight vector, per-leaf tensordot;
    * ``"coordinate"`` — no weights; the rule is purely coordinate-local
      over the raw stack (median / trimmed mean);
    * ``"bulyan"``     — (θ, n) extraction + aggregate weight matrices and
      the β count for the coordinate phase.

    Every field is either a static python int/str or an array whose shape
    depends only on (n, f) — never on d — so plans jit cleanly and replicate
    for free.
    """

    kind: str
    n: int
    f: int
    weights: Optional[Array] = None       # (n,) for kind == "weighted"
    w_ext: Optional[Array] = None         # (theta, n) for kind == "bulyan"
    w_agr: Optional[Array] = None         # (theta, n) for kind == "bulyan"
    beta: int = 0

    # ------------------------------------------------------------ telemetry
    def selection_weights(self) -> Array:
        """Per-worker selection mass as one convex (n,) fp32 vector.

        * ``weighted`` — the plan's weight vector itself;
        * ``bulyan``   — the mean over extraction rounds of the (θ, n)
          aggregate-weight rows (each row convex, so the mean is too): the
          mass each worker contributes to the values entering the coordinate
          phase;
        * ``mean`` / ``coordinate`` — uniform 1/n (every worker's value
          participates; coordinate rules have no worker-level selection).
        """
        if self.kind == "weighted":
            return self.weights.astype(jnp.float32)
        if self.kind == "bulyan":
            return jnp.mean(self.w_agr.astype(jnp.float32), axis=0)
        return jnp.full((self.n,), 1.0 / self.n, jnp.float32)

    def diagnostics(self, stats: Optional[AggStats] = None) -> Dict[str, Array]:
        """Jit-safe per-round diagnostics of *why* the plan chose what it did.

        Returns a dict of fp32 arrays whose shapes depend only on (n, f):

        * ``selection``      — convex (n,) selection mass per worker;
        * ``byz_mass``       — scalar: mass on the first f rows (byzantine
          rows come first by the ``inject_byzantine`` convention, so under
          attack this is the adversary's captured share);
        * ``score_spectrum`` — (n,) ascending Krum scores (needs ``stats``
          with the distance matrix; -inf-free, +inf for dead entries);
        * ``score_gap``      — scalar: min score among zero-mass workers
          minus max score among selected ones — the margin by which the
          selection boundary held (0 when everyone is selected);
        * ``mean_dist``      — scalar: mean off-diagonal pairwise sq-dist.

        Score fields are omitted when ``stats``/``stats.dists`` is absent.
        The suspicion EMA built on these lives in ``repro.sim.telemetry``
        (it needs cross-step state a single plan does not have).
        """
        sel = self.selection_weights()
        byz = jnp.sum(sel[: self.f]) if self.f else jnp.zeros((), jnp.float32)
        out: Dict[str, Array] = {"selection": sel, "byz_mass": byz}
        if stats is not None and stats.dists is not None:
            scores = G.krum_scores(stats.dists, self.f)
            picked = sel > 0.0
            sel_max = jnp.max(jnp.where(picked, scores, -jnp.inf))
            rej_min = jnp.min(jnp.where(picked, jnp.inf, scores))
            gap = jnp.where(jnp.all(picked), 0.0, rej_min - sel_max)
            n = stats.dists.shape[0]
            off = jnp.sum(stats.dists) / (n * (n - 1)) if n > 1 else \
                jnp.zeros((), jnp.float32)
            out.update(score_spectrum=jnp.sort(scores),
                       score_gap=gap.astype(jnp.float32),
                       mean_dist=off.astype(jnp.float32))
        return out


# --------------------------------------------------------------- leaf math
def _leaf2d(x: Array) -> Array:
    """(n, ...) -> (n, numel) view — Pallas/coord-chunk paths only.

    Under pjit, reshaping a param-dim-sharded leaf is NOT sharding
    preserving (GSPMD replicates the flattened stack); the default paths
    operate on the unreshaped leaves via tensordot.
    """
    return x.reshape((x.shape[0], -1))


def _param_axes(leaf: Array):
    return tuple(range(1, leaf.ndim))


def _weighted_mean_leaf(w: Array, leaf: Array) -> Array:
    """(n,) weights (summing to 1) applied over the worker axis of a leaf."""
    x = leaf.astype(jnp.float32)
    return jnp.tensordot(w, x, axes=(0, 0)).astype(leaf.dtype)


def _bulyan_leaf(w_ext: Array, w_agr: Array, beta: int,
                 leaf: Array, coord_chunk: int = 0,
                 use_pallas: bool = False,
                 fused: "bool | str" = True) -> Array:
    """Apply an extraction plan + coordinate phase to one gradient leaf.

    Default path is sharding-preserving: (theta, n) @ (n, ...) tensordots
    keep the parameter-dim sharding, and the coordinate phase is purely
    elementwise/axis-0 over (theta, ...).

    With ``use_pallas`` and ``fused`` (``True`` or ``"force"``, which
    means the same) the apply phase runs in the ``fused_select`` kernel
    at every leaf size (extraction einsums + coordinate phase per d-tile
    in VMEM, no (θ, numel) HBM intermediates); ``fused=False`` keeps the
    two-step Pallas path (materialised einsums + ``coord_select``) for
    benchmarking the fusion win.
    """
    if use_pallas and fused:
        from repro.kernels import ops as kops
        x = _leaf2d(leaf).astype(jnp.float32)      # (n, numel)
        out = kops.fused_select(x, w_ext, w_agr, beta)
        return out.reshape(leaf.shape[1:]).astype(leaf.dtype)

    if use_pallas or coord_chunk:
        x = _leaf2d(leaf).astype(jnp.float32)      # (n, numel)

        def phase(xc: Array) -> Array:             # (n, c) -> (c,)
            # HIGHEST: substrate parity — the fused kernel contracts at
            # HIGHEST, and g_ext feeds the selection-deciding median
            g_ext = jnp.matmul(w_ext, xc,
                               precision=jax.lax.Precision.HIGHEST)
            g_agr = jnp.matmul(w_agr, xc,
                               precision=jax.lax.Precision.HIGHEST)
            if use_pallas:
                from repro.kernels import ops as kops
                return kops.coord_select(g_ext, g_agr, beta)
            return G.bulyan_coordinate_phase(g_ext, g_agr, beta)

        numel = x.shape[1]
        if coord_chunk and numel > coord_chunk:
            pad = (-numel) % coord_chunk
            xp = jnp.pad(x, ((0, 0), (0, pad)))
            chunks = xp.reshape(x.shape[0], -1, coord_chunk).transpose(1, 0, 2)
            out = jax.lax.map(phase, chunks).reshape(-1)[:numel]
        else:
            out = phase(x)
        return out.reshape(leaf.shape[1:]).astype(leaf.dtype)

    x = leaf.astype(jnp.float32)
    g_ext = jnp.tensordot(w_ext, x, axes=(1, 0),   # (theta, ...)
                          precision=jax.lax.Precision.HIGHEST)
    g_agr = jnp.tensordot(w_agr, x, axes=(1, 0),
                          precision=jax.lax.Precision.HIGHEST)
    return G.bulyan_coordinate_phase(g_ext, g_agr, beta).astype(leaf.dtype)


def _sharded_apply_leaf(plan: "AggPlan", leaf: Array, ctx: MeshContext,
                        coordinate_fn=None, *, use_pallas: bool = False,
                        fused: "bool | str" = True,
                        row_mult: Optional[Array] = None) -> Array:
    """Mesh-native apply of one plan to one leaf (DESIGN.md §10).

    The leaf's flattened d axis is sharded over ``ctx.model_axis`` and the
    worker axis over ``ctx.worker_axes``; inside the shard_map each device
    all-gathers the worker rows of its d-shard — the one worker→model
    reshard the pipeline admits — and runs the coordinate phase purely
    locally, so no device ever holds more than (n, d/M) of the stack and
    the model axis pays zero collectives after the gather.

    With ``row_mult`` the leaf is a quantized wire *payload* (int8/bf16)
    and the (n,) per-row dequant multipliers are applied after the gather
    — the §9 decode invariant ``payload.astype(f32) * mult[row]`` runs
    per shard, so the fp32 stack never exists replicated; the result is
    fp32 (the decoded dtype), not the payload dtype.

    Coordinate-kind plans (median / trimmed mean) shard only d: zero-row
    worker padding would perturb order statistics, and their apply never
    mixes workers with weights that could mask padding.
    """
    n = leaf.shape[0]
    M = ctx.model_size
    lead = ctx.worker_entry
    kind = plan.kind
    out_dtype = jnp.float32 if row_mult is not None else leaf.dtype
    x = _leaf2d(leaf)                                  # (n, numel)
    if row_mult is None:
        x = x.astype(jnp.float32)
    numel = x.shape[1]
    d_pad = -(-numel // M) * M
    x = jnp.pad(x, ((0, 0), (0, d_pad - numel)))
    model = ctx.model_axis

    def dequant(rows, mult):
        if mult is None:
            return rows
        return rows.astype(jnp.float32) * mult[:, None]

    if kind == "coordinate":
        fn = _shard_map(
            lambda xl: coordinate_fn(plan, dequant(xl, row_mult)), ctx,
            (P(None, model),), P(model))
        out = fn(x)
        return out[:numel].reshape(leaf.shape[1:]).astype(out_dtype)

    if kind not in ("mean", "weighted", "bulyan"):
        raise ValueError(f"unknown plan kind {kind!r}")
    W = ctx.worker_size
    n_pad = -(-n // W) * W
    x = _pad_rows(x, n_pad)
    mult_pad = None if row_mult is None else \
        jnp.pad(row_mult.astype(jnp.float32), (0, n_pad - n))
    if kind == "weighted":
        w = jnp.pad(plan.weights.astype(jnp.float32), (0, n_pad - n))
    elif kind == "bulyan":
        w_ext = jnp.pad(plan.w_ext, ((0, 0), (0, n_pad - n)))
        w_agr = jnp.pad(plan.w_agr, ((0, 0), (0, n_pad - n)))

    def local(xl):                                     # (n_loc, d_loc)
        xfull = jax.lax.all_gather(xl, ctx.worker_axes, axis=0, tiled=True)
        xfull = dequant(xfull, mult_pad)
        if kind == "mean":
            return jnp.sum(xfull, axis=0) / n
        if kind == "weighted":
            return jnp.tensordot(w, xfull, axes=(0, 0))
        if use_pallas and fused:
            from repro.kernels import ops as kops
            return kops.fused_select(xfull, w_ext, w_agr, plan.beta)
        g_ext = jnp.matmul(w_ext, xfull,
                           precision=jax.lax.Precision.HIGHEST)
        g_agr = jnp.matmul(w_agr, xfull,
                           precision=jax.lax.Precision.HIGHEST)
        if use_pallas:
            from repro.kernels import ops as kops
            return kops.coord_select(g_ext, g_agr, plan.beta)
        return G.bulyan_coordinate_phase(g_ext, g_agr, plan.beta)

    fn = _shard_map(local, ctx, (P(lead, model),), P(model))
    out = fn(x)
    return out[:numel].reshape(leaf.shape[1:]).astype(out_dtype)


def _sharded_apply_encoded(plan: "AggPlan", enc, ctx: MeshContext,
                           coordinate_fn=None, *, use_pallas: bool = False,
                           fused: "bool | str" = True) -> PyTree:
    """Sharded apply straight off an ``EncodedGrads`` container.

    Leaves whose codec admits the dequant form (int8/bf16 payload × one
    fp32 multiplier per worker row — §9) shard the *payload* columns over
    the model axis and dequantize per shard inside the shard_map, so the
    replicated fp32 (n, d) stack never materializes.  Codecs without the
    form (identity — already fp32; top-k — the index scatter is not
    column-local) decode that leaf replicated first.
    """
    from repro.comm import codecs as CC
    codec = CC.get_codec(enc.spec)
    p_leaves, treedef = jax.tree.flatten(enc.payload)
    s_leaves = jax.tree.leaves(enc.sidecar) \
        if enc.sidecar is not None else [None] * len(p_leaves)
    out = []
    for p, s, shape in zip(p_leaves, s_leaves, enc.shapes):
        form = codec.dequant_form(p, s)
        if form is not None:
            payload2d, mult = form
            out.append(_sharded_apply_leaf(
                plan, payload2d.reshape(shape), ctx, coordinate_fn,
                use_pallas=use_pallas, fused=fused, row_mult=mult))
        else:
            g = codec.decode_leaf(_leaf2d(p), s, shape).reshape(shape)
            out.append(_sharded_apply_leaf(
                plan, g, ctx, coordinate_fn,
                use_pallas=use_pallas, fused=fused))
    return jax.tree.unflatten(treedef, out)


# ==========================================================================
# the Aggregator protocol + registry
# ==========================================================================
def _scoped_plan(plan):
    def scoped(self, stats: AggStats) -> AggPlan:
        with scope("plan"):
            return plan(self, stats)
    return functools.wraps(plan)(scoped)


class Aggregator:
    """Two-phase GAR: ``plan`` on the (n, n) statistics, ``apply`` on d.

    A subclass's own ``plan`` traces under the ``robust.plan`` scope
    wherever it is called from, and ``apply`` under ``robust.apply``.

    Capability flags (class attributes):
    * ``needs_dists``       — plan consumes the pairwise-distance matrix;
    * ``coordinate_local``  — apply never mixes coordinates (shards freely);
    * ``min_n(f)``          — the paper's resilience precondition, with its
      human-readable ``min_n_formula`` for error messages.
    """

    name: str = ""
    needs_dists: bool = False
    coordinate_local: bool = True
    min_n_formula: str = "1"

    @staticmethod
    def min_n(f: int) -> int:
        return 1

    def __init_subclass__(cls, **kw):
        super().__init_subclass__(**kw)
        if "plan" in cls.__dict__:
            cls.plan = _scoped_plan(cls.__dict__["plan"])

    # ------------------------------------------------------------- phases
    def validate(self, n: int, f: int) -> None:
        # the one n-vs-f gate, shared with the hierarchical per-level
        # budget checks (theory.split_f_budget / repro.hier)
        from repro.core import theory
        theory.check_level(n, f, rule=self.name, need=self.min_n(f),
                           formula=self.min_n_formula)

    def plan(self, stats: AggStats) -> AggPlan:
        raise NotImplementedError

    def apply(self, plan: AggPlan, grads: PyTree, *, coord_chunk: int = 0,
              use_pallas: bool = False, fused: "bool | str" = True,
              mesh_ctx: Optional[MeshContext] = None) -> PyTree:
        """Plan application — shared across rules, dispatched on plan.kind.

        With ``use_pallas`` the bulyan kind takes the fully fused kernel
        path (one HBM read per leaf, no (θ, d) intermediates) at every
        leaf size; ``fused="force"`` means the same as ``True``, and
        ``fused=False`` benchmarks the two-step Pallas path instead.

        An :class:`EncodedGrads` wire container is decoded first — the
        apply phase mixes values across workers, so it runs on the
        codec-decoded fp32 rows (callers that already hold the decoded
        stack should pass it directly to avoid a second decode).

        With ``mesh_ctx`` every leaf's apply runs mesh-native: the d axis
        shards over the model axis inside a shard_map — no device holds
        more than (n, d/M) of the stack (DESIGN.md §10); wire containers
        with a dequant-form codec shard the quantized payload and decode
        per shard instead of decoding replicated.

        Everything here traces under the ``robust.apply`` scope.
        """
        with scope("apply"):
            enc = _as_encoded(grads)
            if enc is not None:
                if mesh_ctx is not None:
                    return _sharded_apply_encoded(
                        plan, enc, mesh_ctx, self._coordinate_leaf,
                        use_pallas=use_pallas, fused=fused)
                from repro.comm import codecs as CC
                grads = CC.get_codec(enc.spec).decode(enc)
            if mesh_ctx is not None:
                fn = functools.partial(
                    _sharded_apply_leaf, plan, ctx=mesh_ctx,
                    coordinate_fn=self._coordinate_leaf,
                    use_pallas=use_pallas, fused=fused)
                return jax.tree.map(lambda x: fn(x), grads)
            if plan.kind == "mean":
                return jax.tree.map(lambda x: jnp.mean(x, axis=0), grads)
            if plan.kind == "weighted":
                return jax.tree.map(functools.partial(
                    _weighted_mean_leaf, plan.weights), grads)
            if plan.kind == "bulyan":
                fn = functools.partial(_bulyan_leaf, plan.w_ext, plan.w_agr,
                                       plan.beta, coord_chunk=coord_chunk,
                                       use_pallas=use_pallas, fused=fused)
                return jax.tree.map(fn, grads)
            if plan.kind == "coordinate":
                return jax.tree.map(
                    functools.partial(self._coordinate_leaf, plan), grads)
            raise ValueError(f"unknown plan kind {plan.kind!r}")

    def _coordinate_leaf(self, plan: AggPlan, leaf: Array) -> Array:
        raise NotImplementedError

    # --------------------------------------------------------- convenience
    def __call__(self, grads: PyTree, f: int, *,
                 dists: Optional[Array] = None, coord_chunk: int = 0,
                 use_pallas: bool = False,
                 mesh_ctx: Optional[MeshContext] = None) -> PyTree:
        stats = compute_stats(grads, f, needs_dists=self.needs_dists,
                              use_pallas=use_pallas, dists=dists,
                              mesh_ctx=mesh_ctx)
        self.validate(stats.n, stats.f)
        return self.apply(self.plan(stats), grads, coord_chunk=coord_chunk,
                          use_pallas=use_pallas, mesh_ctx=mesh_ctx)


# ==========================================================================
# the shared aggregation backend (plan service + apply service)
# ==========================================================================
@dataclasses.dataclass(frozen=True)
class AggregatorBackend:
    """One bound stats→validate→plan→apply pipeline, shared by every
    consumer (DESIGN.md §13).

    The trainers (``dist.trainer``), the robust serving ensemble
    (``dist.serving.make_robust_serve_step``) and the async bounded-
    staleness service (``repro.serve``) all aggregate through the same
    instance shape: ``plan_stats`` is the *plan service* (O(n²) on the
    replicated statistics, d-free), ``apply`` the *apply service*
    (sharding-preserving einsums + coordinate phase over d).  Splitting
    the two is what lets the async service reuse a previous round's plan
    while still applying it to the freshest buffered gradients.

    Frozen and hashable (``mesh_ctx`` is pure metadata), so step builders
    close over a backend and jit caches key on its configuration.
    """

    gar: str
    f: int
    use_pallas: bool = False
    coord_chunk: int = 0
    fused: "bool | str" = True
    needs_dists: bool = False          # force stats for distance-free rules
    mesh_ctx: Optional[MeshContext] = None
    # observability switchboard (repro.obs.ObsConfig, frozen+hashable):
    # every consumer of a backend — trainers, async service, hier tree —
    # reads the same config, so instrumentation can't half-apply.  None
    # (the default) keeps every step builder on the uninstrumented path.
    obs: Optional[Any] = None

    @classmethod
    def for_config(cls, rcfg, **overrides) -> "AggregatorBackend":
        """Build from a ``RobustConfig`` (gar / f / use_pallas)."""
        kw = dict(gar=rcfg.gar, f=rcfg.f, use_pallas=rcfg.use_pallas)
        kw.update(overrides)
        return cls(**kw)

    @property
    def aggregator(self) -> "Aggregator":
        return get_aggregator(self.gar)

    def stats(self, grads: PyTree, *,
              dists: Optional[Array] = None) -> AggStats:
        agg = self.aggregator
        return compute_stats(grads, self.f,
                             needs_dists=agg.needs_dists or self.needs_dists,
                             use_pallas=self.use_pallas, dists=dists,
                             mesh_ctx=self.mesh_ctx)

    def plan(self, stats: AggStats) -> AggPlan:
        """The plan service: validate + selection on the statistics only."""
        agg = self.aggregator
        agg.validate(stats.n, stats.f)
        return agg.plan(stats)

    def plan_stats(self, grads: PyTree, *, dists: Optional[Array] = None
                   ) -> Tuple[AggPlan, AggStats]:
        stats = self.stats(grads, dists=dists)
        return self.plan(stats), stats

    def apply(self, plan: AggPlan, grads: PyTree) -> PyTree:
        """The apply service: one plan over the d axis of a stack."""
        return self.aggregator.apply(plan, grads,
                                     coord_chunk=self.coord_chunk,
                                     use_pallas=self.use_pallas,
                                     fused=self.fused,
                                     mesh_ctx=self.mesh_ctx)

    def __call__(self, grads: PyTree) -> PyTree:
        plan, _ = self.plan_stats(grads)
        return self.apply(plan, grads)


def select_plan(pred: Array, on_true: AggPlan, on_false: AggPlan) -> AggPlan:
    """Jit-safe plan choice: ``pred ? on_true : on_false`` over the data
    arrays of two same-kind plans (meta fields — kind/n/f/beta — must
    match; they do whenever both came from the same backend).  This is how
    the async service degrades an inadmissible round to the previous
    round's plan without changing any traced shape."""
    with scope("plan"):
        return jax.tree.map(lambda a, b: jnp.where(pred, a, b),
                            on_true, on_false)


REGISTRY: Dict[str, Aggregator] = {}


def register_gar(cls):
    """Class decorator: instantiate and register a GAR by its ``name``."""
    inst = cls()
    if not inst.name:
        raise ValueError(f"{cls.__name__} must set a registry name")
    if inst.name in REGISTRY:
        # every consumer dispatches by name; silent replacement of e.g.
        # multi_bulyan would change results with no indication why
        raise ValueError(
            f"GAR {inst.name!r} is already registered "
            f"({type(REGISTRY[inst.name]).__name__}); pick a distinct name "
            f"or REGISTRY.pop() the old rule first")
    REGISTRY[inst.name] = inst
    return cls


def get_aggregator(name: str) -> Aggregator:
    try:
        return REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown GAR {name!r}; available: {sorted(REGISTRY)}") from None


def available_gars() -> Tuple[str, ...]:
    return tuple(sorted(REGISTRY))


# ==========================================================================
# the seven rules
# ==========================================================================
@register_gar
class Average(Aggregator):
    """Plain averaging — fastest, non-byzantine-resilient baseline."""

    name = "average"

    def plan(self, stats: AggStats) -> AggPlan:
        return AggPlan(kind="mean", n=stats.n, f=stats.f)


@register_gar
class CoordinateMedian(Aggregator):
    """Coordinate-wise median (the MEDIAN baseline of §V)."""

    name = "median"

    def plan(self, stats: AggStats) -> AggPlan:
        return AggPlan(kind="coordinate", n=stats.n, f=stats.f)

    def _coordinate_leaf(self, plan: AggPlan, leaf: Array) -> Array:
        return G._median_axis0(leaf.astype(jnp.float32)).astype(leaf.dtype)


@register_gar
class TrimmedMean(Aggregator):
    """Coordinate-wise trimmed mean: drop the f largest and f smallest."""

    name = "trimmed_mean"
    min_n_formula = "2f+1"

    @staticmethod
    def min_n(f: int) -> int:
        return 2 * f + 1

    def plan(self, stats: AggStats) -> AggPlan:
        if stats.n <= 2 * stats.f:
            raise ValueError(
                f"trimmed_mean needs n > 2f (n={stats.n}, f={stats.f})")
        return AggPlan(kind="coordinate", n=stats.n, f=stats.f)

    def _coordinate_leaf(self, plan: AggPlan, leaf: Array) -> Array:
        s = G._sort_by_value(leaf.astype(jnp.float32), axis=0)
        return jnp.mean(s[plan.f:plan.n - plan.f], axis=0).astype(leaf.dtype)


class _KrumFamily(Aggregator):
    needs_dists = True
    coordinate_local = False
    min_n_formula = "2f+3"
    _m_select: Optional[int] = None       # None -> the paper's m̃ = n-f-2

    @staticmethod
    def min_n(f: int) -> int:
        return 2 * f + 3

    def plan(self, stats: AggStats) -> AggPlan:
        n, f = stats.n, stats.f
        self.validate(n, f)
        m = self._m_select if self._m_select is not None else n - f - 2
        # selection is piecewise-constant in G: the aggregate's gradient
        # flows through the selected average only, never through the plan
        scores = jax.lax.stop_gradient(G.krum_scores(stats.dists, f))
        mask = G._select_smallest_mask(scores, m)
        w = mask.astype(jnp.float32)
        return AggPlan(kind="weighted", n=n, f=f, weights=w / jnp.sum(w))


@register_gar
class Krum(_KrumFamily):
    """Krum (Blanchard et al. 2017): the single best-scored gradient."""

    name = "krum"
    _m_select = 1


@register_gar
class MultiKrum(_KrumFamily):
    """MULTI-KRUM (§III): average of the m̃ = n-f-2 best-scored."""

    name = "multi_krum"


class _BulyanFamily(Aggregator):
    needs_dists = True
    coordinate_local = False
    min_n_formula = "4f+3"
    _multi = True

    @staticmethod
    def min_n(f: int) -> int:
        return 4 * f + 3

    def plan(self, stats: AggStats) -> AggPlan:
        n, f = stats.n, stats.f
        self.validate(n, f)
        theta = n - 2 * f - 2
        beta = theta - 2 * f
        w_ext, w_agr = G.extraction_plan(stats.dists, f, theta,
                                         multi=self._multi)
        return AggPlan(kind="bulyan", n=n, f=f, w_ext=w_ext, w_agr=w_agr,
                       beta=beta)


@register_gar
class Bulyan(_BulyanFamily):
    """Classic BULYAN: iterated Krum extraction + coordinate phase."""

    name = "bulyan"
    _multi = False


@register_gar
class MultiBulyan(_BulyanFamily):
    """MULTI-BULYAN (Algorithm 1): BULYAN over MULTI-KRUM aggregates."""

    name = "multi_bulyan"


# ==========================================================================
# high-level entry points (what the shims delegate to)
# ==========================================================================
def aggregate_tree(grads: PyTree, f: int, name: str = "multi_bulyan", *,
                   coord_chunk: int = 0, use_pallas: bool = False,
                   fused: "bool | str" = True, dists: Optional[Array] = None,
                   mesh_ctx: Optional[MeshContext] = None) -> PyTree:
    """Aggregate a stacked gradient pytree with the named registered rule."""
    agg = get_aggregator(name)
    stats = compute_stats(grads, f, needs_dists=agg.needs_dists,
                          use_pallas=use_pallas, dists=dists,
                          mesh_ctx=mesh_ctx)
    agg.validate(stats.n, stats.f)
    return agg.apply(agg.plan(stats), grads, coord_chunk=coord_chunk,
                     use_pallas=use_pallas, fused=fused, mesh_ctx=mesh_ctx)


def aggregate_matrix(Gm: Array, f: int, name: str = "multi_bulyan", *,
                     dists: Optional[Array] = None) -> Array:
    """(n, d) stack -> (d,) aggregate: the single-leaf pytree special case."""
    return aggregate_tree(Gm, f, name, dists=dists)


# ==========================================================================
# pre-aggregation transforms
# ==========================================================================
class Transform:
    """A composable stage rewriting the stacked gradients before the GAR.

    ``stateful`` transforms carry a per-worker state pytree across steps
    (see :func:`init_transform_states`); ``needs_dists`` ones receive an
    :class:`AggStats` with the distance matrix of the *current* stack.
    Signature: ``(grads, stats=None, state=None, key=None) -> (grads, state)``.
    """

    name: str = ""
    stateful: bool = False
    needs_dists: bool = False

    def init(self, grads: PyTree) -> PyTree:
        raise NotImplementedError(f"{self.name} is stateless")

    def __call__(self, grads: PyTree, *, stats: Optional[AggStats] = None,
                 state: Optional[PyTree] = None,
                 key: Optional[Array] = None) -> Tuple[PyTree, PyTree]:
        raise NotImplementedError


@dataclasses.dataclass(frozen=True)
class ClipByNorm(Transform):
    """Per-worker l2 clipping: ||g_i|| <= max_norm (static-shape, jit-safe).

    A cheap prefilter against magnitude attacks — the GAR still provides
    the directional guarantee.
    """

    max_norm: float = 1.0
    name: str = "clip"

    def __call__(self, grads, *, stats=None, state=None, key=None):
        norms = jnp.sqrt(jnp.maximum(tree_sq_norms(grads), 1e-30))   # (n,)
        scale = jnp.minimum(1.0, self.max_norm / norms)              # (n,)

        def clip_leaf(x):
            s = scale.reshape((-1,) + (1,) * (x.ndim - 1))
            return (x.astype(jnp.float32) * s).astype(x.dtype)

        return jax.tree.map(clip_leaf, grads), state


@dataclasses.dataclass(frozen=True)
class WorkerMomentum(Transform):
    """Resilient averaging of momentums (Farhadkhani et al. 2022).

    Each worker's gradient is replaced by its exponential momentum
    m_i <- β·m_i + g_i before aggregation; the GAR then runs on momentums,
    which shrinks the honest-worker variance the no-free-lunch bound (§VI)
    is driven by.
    """

    beta: float = 0.9
    name: str = "worker_momentum"
    stateful: bool = True

    def init(self, grads: PyTree) -> PyTree:
        return jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), grads)

    def __call__(self, grads, *, stats=None, state=None, key=None):
        if state is None:
            raise ValueError("worker_momentum needs a state pytree; "
                             "seed it with init_transform_states()")
        new = jax.tree.map(
            lambda m, g: self.beta * m + g.astype(jnp.float32), state, grads)
        out = jax.tree.map(lambda m, g: m.astype(g.dtype), new, grads)
        return out, new


@dataclasses.dataclass(frozen=True)
class NearestNeighborMix(Transform):
    """Replace g_i by the mean of its k nearest neighbours (self included).

    A pre-aggregation smoothing step (NNM, Allouah et al. 2023 style) that
    provably tightens the variance condition the paper's §VI bound depends
    on.  Plan-shaped: the (n, n) mixing matrix depends only on distances.
    """

    k: int = 3
    name: str = "nn_mix"
    needs_dists: bool = True

    def __call__(self, grads, *, stats=None, state=None, key=None):
        if stats is None or stats.dists is None:
            raise ValueError("nn_mix needs AggStats with the distance matrix")
        n = stats.n
        k = min(self.k, n)
        # rank each row's distances (self-distance 0 ranks first)
        order = jnp.argsort(stats.dists, axis=1)
        ranks = jnp.argsort(order, axis=1)
        W = (ranks < k).astype(jnp.float32) / float(k)        # (n, n)
        mix = functools.partial(_mix_leaf, W)
        return jax.tree.map(mix, grads), state


def _mix_leaf(W: Array, leaf: Array) -> Array:
    x = leaf.astype(jnp.float32)
    return jnp.tensordot(W, x, axes=(1, 0)).astype(leaf.dtype)


TRANSFORMS: Dict[str, Callable[..., Transform]] = {
    "clip": ClipByNorm,
    "worker_momentum": WorkerMomentum,
    "nn_mix": NearestNeighborMix,
}


def init_transform_states(transforms: Sequence[Transform],
                          grads_like: PyTree) -> Tuple[PyTree, ...]:
    """Initial state tuple (one entry per transform; None when stateless)."""
    return tuple(t.init(grads_like) if t.stateful else None
                 for t in transforms)


def apply_transforms(grads: PyTree, transforms: Sequence[Transform],
                     states: Optional[Sequence[PyTree]] = None, *,
                     key: Optional[Array] = None,
                     use_pallas: bool = False
                     ) -> Tuple[PyTree, Tuple[PyTree, ...]]:
    """Run the transform pipeline; returns (grads, new_states)."""
    if not transforms:
        return grads, ()
    if states is None:
        states = (None,) * len(transforms)
    new_states = []
    f0 = 0  # transforms are rule-agnostic; stats carry distances only
    for i, (t, st) in enumerate(zip(transforms, states)):
        stats = None
        if t.needs_dists:
            stats = compute_stats(grads, f0, needs_dists=True,
                                  use_pallas=use_pallas)
        k = jax.random.fold_in(key, i) if key is not None else None
        grads, st = t(grads, stats=stats, state=st, key=k)
        new_states.append(st)
    return grads, tuple(new_states)
