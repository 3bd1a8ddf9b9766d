"""Substrate tests: optimizers, schedules, data, checkpointing, losses,
attention primitives, and the fused-vs-unfused aggregation substrates."""
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.checkpoint import latest_step, restore, save
from repro.data import classification_batches, lm_batches, make_lm_batch
from repro.models import attention as A
from repro.models import modules as M
from repro.models.losses import chunked_xent
from repro.optim import adamw, constant, sgd, warmup_cosine
from repro.configs.base import ArchConfig

KEY = jax.random.key(0)


# ------------------------------------------------------------- optimizers
def test_sgd_momentum_matches_manual():
    params = {"w": jnp.asarray([1.0, 2.0])}
    grads = {"w": jnp.asarray([0.5, -1.0])}
    opt = sgd(momentum=0.9)
    st = opt.init(params)
    p1, st = opt.update(grads, st, params, 0.1)
    np.testing.assert_allclose(np.asarray(p1["w"]), [1 - 0.05, 2 + 0.1])
    p2, st = opt.update(grads, st, p1, 0.1)
    # m2 = 0.9*0.5 + 0.5 = 0.95 ; p = 0.95 - 0.1*0.95
    np.testing.assert_allclose(np.asarray(p2["w"])[0], 0.95 - 0.095,
                               rtol=1e-6)


def test_adamw_first_step_is_lr_sized():
    params = {"w": jnp.asarray([0.0])}
    grads = {"w": jnp.asarray([123.0])}
    opt = adamw()
    st = opt.init(params)
    p1, _ = opt.update(grads, st, params, 1e-3)
    # bias-corrected first step ≈ -lr * sign(g)
    np.testing.assert_allclose(np.asarray(p1["w"]), [-1e-3], rtol=1e-4)


def test_warmup_cosine_shape():
    fn = warmup_cosine(1.0, warmup=10, total_steps=100)
    assert float(fn(jnp.int32(0))) == 0.0
    assert float(fn(jnp.int32(10))) == pytest.approx(1.0, rel=1e-3)
    assert float(fn(jnp.int32(100))) < 0.2


# ------------------------------------------------------------------ data
def test_lm_batches_deterministic_and_learnable():
    a = next(lm_batches(64, 4, 16, seed=5))
    b = next(lm_batches(64, 4, 16, seed=5))
    np.testing.assert_array_equal(np.asarray(a["tokens"]),
                                  np.asarray(b["tokens"]))
    # labels are next tokens
    np.testing.assert_array_equal(np.asarray(a["tokens"][:, 1:]),
                                  np.asarray(a["labels"][:, :-1]))
    # bigram automaton: each token has <= branching successors => the
    # empirical conditional entropy is far below uniform
    batch = make_lm_batch(KEY, 64, 64, 128, seed=5)
    toks = np.asarray(batch["tokens"])
    pairs = set(zip(toks[:, :-1].ravel().tolist(), toks[:, 1:].ravel().tolist()))
    succ = {}
    for a_, b_ in pairs:
        succ.setdefault(a_, set()).add(b_)
    assert max(len(v) for v in succ.values()) <= 4


def test_classification_batches_separable():
    it = classification_batches(8, 3, 64, seed=1, noise=0.1)
    x, y = next(it)
    assert x.shape == (64, 8) and y.shape == (64,)
    # same-class points cluster: intra-class distance << inter-class
    x, y = np.asarray(x), np.asarray(y)
    mus = np.stack([x[y == c].mean(0) for c in range(3)])
    intra = np.mean([np.linalg.norm(x[y == c] - mus[c], axis=1).mean()
                     for c in range(3)])
    inter = np.linalg.norm(mus[0] - mus[1])
    assert inter > intra


# ------------------------------------------------------------ checkpoints
def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "b": {"c": jnp.asarray([1, 2], jnp.int32)},
            "d": [jnp.ones((4,), jnp.bfloat16)]}
    d = str(tmp_path / "ck")
    save(d, 7, tree)
    assert latest_step(d) == 7
    back = restore(d, 7, jax.tree.map(jnp.zeros_like, tree))
    for x, y in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        assert x.dtype == y.dtype
        np.testing.assert_array_equal(np.asarray(x, np.float32),
                                      np.asarray(y, np.float32))


def test_checkpoint_shape_mismatch_raises(tmp_path):
    d = str(tmp_path / "ck")
    save(d, 1, {"w": jnp.zeros((2, 2))})
    with pytest.raises(ValueError):
        restore(d, 1, {"w": jnp.zeros((3, 3))})


# ---------------------------------------------------------------- losses
@pytest.mark.parametrize("chunk", [4, 16, 1 << 20])
def test_chunked_xent_matches_naive(chunk):
    b, s, d, v = 2, 9, 8, 32
    x = jax.random.normal(KEY, (b, s, d), jnp.float32)
    labels = jax.random.randint(jax.random.key(1), (b, s), 0, v)
    w = jax.random.normal(jax.random.key(2), (d, v), jnp.float32)
    got = chunked_xent(x, labels, {"lm_head": {"w": w}}, tied=False,
                       chunk=chunk)
    logits = x @ w
    lf = logits.astype(jnp.float32)
    want = jnp.mean(jax.nn.logsumexp(lf, -1) -
                    jnp.take_along_axis(lf, labels[..., None], -1)[..., 0])
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)


def test_chunked_xent_mask():
    b, s, d, v = 1, 6, 4, 16
    x = jax.random.normal(KEY, (b, s, d), jnp.float32)
    labels = jax.random.randint(jax.random.key(1), (b, s), 0, v)
    w = jax.random.normal(jax.random.key(2), (d, v), jnp.float32)
    mask = jnp.asarray([[1, 1, 0, 0, 0, 0]], jnp.float32)
    got = chunked_xent(x, labels, {"lm_head": {"w": w}}, tied=False,
                       mask=mask, chunk=3)
    got_full = chunked_xent(x[:, :2], labels[:, :2],
                            {"lm_head": {"w": w}}, tied=False, chunk=3)
    np.testing.assert_allclose(float(got), float(got_full), rtol=1e-5)


# -------------------------------------------------------------- attention
def test_rope_preserves_norm_and_relativity():
    cfg = ArchConfig(name="t", family="dense", d_model=32, n_heads=2,
                     n_kv_heads=2, rope="full")
    x = jax.random.normal(KEY, (1, 6, 2, 16), jnp.float32)
    pos = jnp.arange(6)[None]
    y = A.apply_rope(x, pos, cfg)
    np.testing.assert_allclose(np.linalg.norm(np.asarray(y), axis=-1),
                               np.linalg.norm(np.asarray(x), axis=-1),
                               rtol=1e-5)
    # relativity: <rope(q,i), rope(k,j)> depends only on i-j
    q = jax.random.normal(jax.random.key(3), (1, 1, 1, 16))
    k = jax.random.normal(jax.random.key(4), (1, 1, 1, 16))
    def dot(i, j):
        qi = A.apply_rope(q, jnp.asarray([[i]]), cfg)
        kj = A.apply_rope(k, jnp.asarray([[j]]), cfg)
        return float(jnp.vdot(qi, kj))
    assert dot(3, 1) == pytest.approx(dot(7, 5), rel=1e-4)


def test_partial_rope_rotates_half():
    cfg = ArchConfig(name="t", family="dense", d_model=32, n_heads=2,
                     n_kv_heads=2, rope="partial", rope_fraction=0.5)
    x = jnp.ones((1, 2, 1, 16), jnp.float32)
    y = A.apply_rope(x, jnp.asarray([[0, 5]]), cfg)
    # second half of head_dim untouched
    np.testing.assert_array_equal(np.asarray(y[..., 8:]),
                                  np.asarray(x[..., 8:]))
    assert not np.allclose(np.asarray(y[0, 1, 0, :8]),
                           np.asarray(x[0, 1, 0, :8]))


def test_attend_full_causality_and_window():
    b, s, h, hd = 1, 8, 2, 4
    q = jax.random.normal(KEY, (b, s, h, hd))
    k = jax.random.normal(jax.random.key(1), (b, s, h, hd))
    v = jax.random.normal(jax.random.key(2), (b, s, h, hd))
    full = A.attend_full(q, k, v, causal=True, chunk_q=4)
    # causality: changing the future does not change the past
    k2 = k.at[:, 6:].set(7.0)
    v2 = v.at[:, 6:].set(7.0)
    full2 = A.attend_full(q, k2, v2, causal=True, chunk_q=4)
    np.testing.assert_allclose(np.asarray(full[:, :6]),
                               np.asarray(full2[:, :6]), rtol=1e-5, atol=1e-5)
    # window=1: each position attends only to itself => out = v
    w1 = A.attend_full(q, k, v, causal=True, window=1, chunk_q=4)
    np.testing.assert_allclose(np.asarray(w1), np.asarray(v), rtol=1e-4,
                               atol=1e-4)


def test_gqa_expand_kv_grouping():
    b, s, hkv, hd, h = 1, 3, 2, 4, 6
    k = jax.random.normal(KEY, (b, s, hkv, hd))
    ke = A._expand_kv(k, h)
    assert ke.shape == (b, s, h, hd)
    # heads 0..2 share kv head 0
    np.testing.assert_array_equal(np.asarray(ke[:, :, 0]),
                                  np.asarray(ke[:, :, 2]))
    np.testing.assert_array_equal(np.asarray(ke[:, :, 3]),
                                  np.asarray(ke[:, :, 5]))


# =================================================================
# fused apply substrate: kernel edge shapes + bitwise agreement with
# the unfused plan/apply path (interpret mode).  The grid covers:
# n not a multiple of 8 (7, 11, 15), d not a multiple of 128 and
# smaller than d_tile (1, 100, 257), the even-θ median branch
# (n=12, f=2 → θ=6), and β = θ (f=0 → β = θ = n-2).
# =================================================================
_RNG_SUB = np.random.default_rng(23)
EDGE_GRID = [(7, 1), (11, 2), (15, 3), (12, 2), (6, 0)]


def _edge_stack(n, d):
    G = _RNG_SUB.normal(size=(n, d)).astype(np.float32)
    G[: max(1, n // 5)] *= 20.0       # some rows far out, like an attack
    return jnp.asarray(G)


@pytest.mark.parametrize("rule", ["multi_krum", "multi_bulyan"])
@pytest.mark.parametrize("n,f", EDGE_GRID)
@pytest.mark.parametrize("d", [100, 257])
def test_fused_apply_bitwise_vs_unfused(rule, n, f, d):
    """Same plan, fused Pallas apply ≡ unfused XLA apply, bit for bit."""
    from repro.core import api
    agg = api.get_aggregator(rule)
    if n < agg.min_n(f):
        pytest.skip("below the rule's resilience precondition")
    G = _edge_stack(n, d)
    stats = api.compute_stats(G, f, needs_dists=agg.needs_dists)
    plan = agg.plan(stats)
    unfused = np.asarray(agg.apply(plan, G, use_pallas=False))
    fused = np.asarray(agg.apply(plan, G, use_pallas=True, fused=True))
    np.testing.assert_array_equal(unfused, fused)


@pytest.mark.parametrize("shape", [(11, 519, 384), (11, 519, 385),
                                   (11, 37, 300)])
def test_fused_apply_bitwise_vs_xla_on_3d_leaves(shape):
    """A 3-D leaf whose trailing dims are not (8, 128)-aligned and whose
    size is no multiple of the kernel's macro block (the first two end in
    a partial block, the last fits in one): the fused apply on the (n, d)
    reshape equals the XLA substrate's apply on the leaf, bit for bit.
    The CPU's XLA dot rounds by the width of its operand, so the XLA side
    runs over the kernel's lane windows (``coord_chunk`` = its d_tile)."""
    from repro.core import api
    from repro.kernels import ops
    n, f = shape[0], 2
    d = int(np.prod(shape[1:]))
    G = _edge_stack(n, d).reshape(shape)
    agg = api.get_aggregator("multi_bulyan")
    plan = agg.plan(api.compute_stats({"w": G}, f, needs_dists=True))
    d_tile, macro = ops.fused_select_tiles(16, d, plan.w_ext.shape[0])
    assert d % macro
    xla = np.asarray(agg.apply(plan, {"w": G}, use_pallas=False,
                               coord_chunk=d_tile)["w"])
    fused = np.asarray(agg.apply(plan, {"w": G}, use_pallas=True)["w"])
    assert fused.shape == shape[1:]
    np.testing.assert_array_equal(xla, fused)


@pytest.mark.parametrize("n,f", EDGE_GRID)
def test_fused_apply_degenerate_width(n, f):
    """d=1 (single coordinate): XLA lowers the unfused einsum to a gemv
    with a different k-reduction order, so agreement is to the last ulp
    rather than bitwise — the fused path itself is tile-invariant."""
    from repro.core import api
    G = _edge_stack(n, 1)
    stats = api.compute_stats(G, f, needs_dists=True)
    plan = api.get_aggregator("multi_bulyan").plan(stats)
    agg = api.get_aggregator("multi_bulyan")
    unfused = np.asarray(agg.apply(plan, G, use_pallas=False))
    fused = np.asarray(agg.apply(plan, G, use_pallas=True, fused=True))
    np.testing.assert_allclose(unfused, fused, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("n,f", [(12, 2), (6, 0)])
def test_fused_apply_theta_branches(n, f):
    """Even-θ median and β = θ hit the fused kernel's special branches."""
    from repro.core import api
    theta = n - 2 * f - 2
    beta = theta - 2 * f
    if f == 0:
        assert beta == theta          # β = θ: selection keeps every row
    else:
        assert theta % 2 == 0         # even-θ median: midpoint average
    G = _edge_stack(n, 257)
    plan = api.get_aggregator("multi_bulyan").plan(
        api.compute_stats(G, f, needs_dists=True))
    assert plan.beta == beta and plan.w_ext.shape == (theta, n)
    fused = np.asarray(api.get_aggregator("multi_bulyan").apply(
        plan, G, use_pallas=True, fused=True))
    unfused = np.asarray(api.get_aggregator("multi_bulyan").apply(
        plan, G, use_pallas=False))
    np.testing.assert_array_equal(unfused, fused)


@pytest.mark.parametrize("n,f", [(11, 2), (12, 2)])
def test_fused_full_pipeline_bitwise_on_trees(n, f):
    """End-to-end aggregate_tree: fused vs two-step Pallas on a pytree,
    sharing the Pallas statistics path (single-pass kernel)."""
    from repro.core import api
    d = 300
    G = _edge_stack(n, d)
    tree = {"a": G[:, :120].reshape(n, 8, 15), "b": {"c": G[:, 120:]}}
    fused = api.aggregate_tree(tree, f, "multi_bulyan", use_pallas=True,
                               fused=True)
    twostep = api.aggregate_tree(tree, f, "multi_bulyan", use_pallas=True,
                                 fused=False)
    for a, b in zip(jax.tree.leaves(fused), jax.tree.leaves(twostep)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_single_pass_stats_matches_two_pass():
    """compute_stats' fused statistics ≡ separate dists + norms passes."""
    from repro.core import api
    n, d = 11, 500
    G = _edge_stack(n, d)
    tree = {"a": G[:, :200], "b": G[:, 200:].reshape(n, 10, 30)}
    stats = api.compute_stats(tree, 2, needs_dists=True, needs_norms=True)
    np.testing.assert_allclose(
        np.asarray(stats.dists), np.asarray(api.tree_pairwise_sqdist(tree)),
        rtol=1e-6, atol=1e-4)
    np.testing.assert_allclose(
        np.asarray(stats.sq_norms), np.asarray(api.tree_sq_norms(tree)),
        rtol=1e-5, atol=1e-4)
    # pallas single-pass agrees with the XLA single-pass
    ds, sq = api.tree_pairwise_stats(tree, use_pallas=True)
    scale = max(float(jnp.max(stats.dists)), 1.0)
    np.testing.assert_allclose(np.asarray(ds), np.asarray(stats.dists),
                               rtol=0, atol=1e-5 * scale)
    np.testing.assert_allclose(np.asarray(sq), np.asarray(stats.sq_norms),
                               rtol=1e-5, atol=1e-5 * scale)
