"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""
import numpy as np
import pytest
import jax.numpy as jnp

from repro.kernels import ops
from repro.kernels.ref import coord_select_ref, pairwise_sqdist_ref

RNG = np.random.default_rng(7)


def _bulyan_plan_weights(n, f):
    """A real extraction plan for an (n, f) pair (θ one-hots + averages)."""
    from repro.core import gar
    d = 64
    G = jnp.asarray(RNG.normal(size=(n, d)).astype(np.float32))
    theta = n - 2 * f - 2
    beta = theta - 2 * f
    w_ext, w_agr = gar.extraction_plan(gar.pairwise_sqdist(G), f, theta)
    return G, w_ext, w_agr, beta


@pytest.mark.parametrize("n", [3, 8, 11, 16, 33])
@pytest.mark.parametrize("d", [1, 100, 257, 2048, 5000])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_pairwise_sqdist_sweep(n, d, dtype):
    x = jnp.asarray(RNG.normal(size=(n, d)).astype(np.float32)).astype(dtype)
    got = ops.pairwise_sqdist(x)
    want = pairwise_sqdist_ref(x)
    assert got.shape == (n, n)
    assert got.dtype == jnp.float32
    scale = max(float(jnp.max(want)), 1.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0, atol=1e-5 * scale)
    assert np.all(np.diag(np.asarray(got)) == 0.0)


@pytest.mark.parametrize("d_tile", [128, 512, 2048])
def test_pairwise_sqdist_tile_invariance(d_tile):
    x = jnp.asarray(RNG.normal(size=(9, 3000)).astype(np.float32))
    got = ops.pairwise_sqdist(x, d_tile=d_tile)
    want = pairwise_sqdist_ref(x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-3)


@pytest.mark.parametrize("theta,beta", [(5, 1), (8, 2), (16, 4), (30, 10),
                                        (7, 7)])
@pytest.mark.parametrize("d", [1, 64, 1000, 2049])
def test_coord_select_sweep(theta, beta, d):
    ge = jnp.asarray(RNG.normal(size=(theta, d)).astype(np.float32))
    ga = jnp.asarray(RNG.normal(size=(theta, d)).astype(np.float32))
    got = ops.coord_select(ge, ga, beta)
    want = coord_select_ref(ge, ga, beta)
    assert got.shape == (d,)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0, atol=1e-5)


def test_coord_select_ties():
    """Equal distances must break ties by row index (matches oracle)."""
    theta, d = 6, 10
    ge = jnp.zeros((theta, d), jnp.float32)
    ga = jnp.ones((theta, d), jnp.float32)      # all equidistant from median 0
    got = ops.coord_select(ge, ga, 3)
    want = coord_select_ref(ge, ga, 3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want))


def test_coord_select_beta_equals_theta_is_mean():
    theta, d = 9, 33
    ge = jnp.asarray(RNG.normal(size=(theta, d)).astype(np.float32))
    ga = jnp.asarray(RNG.normal(size=(theta, d)).astype(np.float32))
    got = ops.coord_select(ge, ga, theta)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(jnp.mean(ga, axis=0)),
                               rtol=1e-5, atol=1e-6)


# -------------------------------------------------------- single-pass stats
@pytest.mark.parametrize("n", [3, 8, 11, 16])
@pytest.mark.parametrize("d", [1, 100, 257, 5000])
def test_pairwise_stats_single_pass(n, d):
    """One HBM read must reproduce both the distance and the norm kernels."""
    x = jnp.asarray(RNG.normal(size=(n, d)).astype(np.float32))
    dists, sq = ops.pairwise_stats(x)
    assert dists.shape == (n, n) and sq.shape == (n,)
    want_d = pairwise_sqdist_ref(x)
    # raw contribution: clamp + zero diagonal is the caller's finalisation
    got_d = np.maximum(np.asarray(dists), 0.0) * (1.0 - np.eye(n))
    scale = max(float(jnp.max(want_d)), 1.0)
    np.testing.assert_allclose(got_d, np.asarray(want_d),
                               rtol=0, atol=1e-5 * scale)
    np.testing.assert_allclose(np.asarray(sq),
                               np.sum(np.asarray(x) ** 2, axis=1),
                               rtol=1e-5, atol=1e-5 * scale)


def test_pairwise_stats_matches_sqdist_kernel_bitwise():
    """Same tile schedule -> identical float accumulation for distances."""
    x = jnp.asarray(RNG.normal(size=(13, 3000)).astype(np.float32))
    dists, _ = ops.pairwise_stats(x, d_tile=512)
    fin = np.maximum(np.asarray(dists), 0.0) * (1.0 - np.eye(13))
    np.testing.assert_array_equal(
        fin.astype(np.float32),
        np.asarray(ops.pairwise_sqdist(x, d_tile=512)))


# ------------------------------------------------------------- fused select
@pytest.mark.parametrize("n,f", [(7, 1), (11, 2), (15, 3), (12, 2)])
@pytest.mark.parametrize("d", [1, 100, 2048, 2500])
def test_fused_select_matches_composed_reference(n, f, d):
    _, w_ext, w_agr, beta = _bulyan_plan_weights(n, f)
    x = jnp.asarray(RNG.normal(size=(n, d)).astype(np.float32))
    got = ops.fused_select(x, w_ext, w_agr, beta)
    ge = jnp.asarray(np.asarray(w_ext) @ np.asarray(x))
    ga = jnp.asarray(np.asarray(w_agr) @ np.asarray(x))
    want = coord_select_ref(ge, ga, beta)
    assert got.shape == (d,)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0, atol=1e-5)


def test_fused_select_tile_invariance():
    n, f = 11, 2
    _, w_ext, w_agr, beta = _bulyan_plan_weights(n, f)
    x = jnp.asarray(RNG.normal(size=(n, 3000)).astype(np.float32))
    base = np.asarray(ops.fused_select(x, w_ext, w_agr, beta, d_tile=2048))
    for d_tile in (128, 512):
        np.testing.assert_allclose(
            np.asarray(ops.fused_select(x, w_ext, w_agr, beta,
                                        d_tile=d_tile)),
            base, rtol=0, atol=1e-5)


def test_fused_select_rejects_bad_shapes():
    x = jnp.zeros((8, 64), jnp.float32)
    w = jnp.zeros((3, 8), jnp.float32)
    with pytest.raises(ValueError, match="beta"):
        ops.fused_select(x, w, w, 0)
    with pytest.raises(ValueError, match="weights must be"):
        ops.fused_select(x, jnp.zeros((3, 7)), jnp.zeros((3, 7)), 1)
    with pytest.raises(ValueError, match="shapes differ"):
        ops.fused_select(x, w, jnp.zeros((4, 8)), 1)


# ------------------------------------------------------ two-level invariance
@pytest.mark.parametrize("n,f", [(7, 1), (11, 2), (15, 3), (12, 2)])
def test_fused_select_two_level_bitwise_vs_single_level(n, f):
    """The macro grid is pure launch geometry: any (d_tile, macro_tile)
    pair — including the policy default — must be bitwise-identical to
    the single-level launch (fused_select is column-independent)."""
    _, w_ext, w_agr, beta = _bulyan_plan_weights(n, f)
    x = jnp.asarray(RNG.normal(size=(n, 257)).astype(np.float32))
    single = np.asarray(ops.fused_select(x, w_ext, w_agr, beta,
                                         d_tile=128, macro_tile=128))
    for macro in (256, 384):
        two = np.asarray(ops.fused_select(x, w_ext, w_agr, beta,
                                          d_tile=128, macro_tile=macro))
        np.testing.assert_array_equal(two, single)
    np.testing.assert_array_equal(
        np.asarray(ops.fused_select(x, w_ext, w_agr, beta)), single)


def test_fused_select_two_level_bitwise_deep_grid():
    """>= 2 macro blocks, each sweeping many inner windows — the d=1e6
    launch shape in miniature, against the windows=1 launch."""
    n, f = 11, 2
    _, w_ext, w_agr, beta = _bulyan_plan_weights(n, f)
    d = 120_000
    dt, macro = ops.fused_select_tiles(16, d, w_ext.shape[0])
    assert macro > dt and -(-d // macro) >= 2   # the regime under test
    x = jnp.asarray(RNG.normal(size=(n, d)).astype(np.float32))
    two = np.asarray(ops.fused_select(x, w_ext, w_agr, beta))
    one = np.asarray(ops.fused_select(x, w_ext, w_agr, beta,
                                      d_tile=dt, macro_tile=dt))
    np.testing.assert_array_equal(two, one)


def test_pairwise_stats_two_level_bitwise():
    """Macro blocks must not change the accumulation order: the inner
    d_tile windows run in global order across macro steps (the first-
    window init + zero-pad tail windows add exact +0.0)."""
    x = jnp.asarray(RNG.normal(size=(13, 3000)).astype(np.float32))
    base_d, base_s = ops.pairwise_stats(x, d_tile=512, macro_tile=512)
    for macro in (1024, 2048):      # 2048 pads d: exercises tail windows
        dd, ss = ops.pairwise_stats(x, d_tile=512, macro_tile=macro)
        np.testing.assert_array_equal(np.asarray(dd), np.asarray(base_d))
        np.testing.assert_array_equal(np.asarray(ss), np.asarray(base_s))


def test_pairwise_stats_two_level_bitwise_deep_grid():
    d = 131_072
    dt, macro = ops._stats_tiles(16, d)
    assert macro > dt and -(-d // macro) >= 2
    x = jnp.asarray(RNG.normal(size=(15, d)).astype(np.float32))
    two_d, two_s = ops.pairwise_stats(x)            # policy launch
    one_d, one_s = ops.pairwise_stats(x, d_tile=dt, macro_tile=dt)
    np.testing.assert_array_equal(np.asarray(two_d), np.asarray(one_d))
    np.testing.assert_array_equal(np.asarray(two_s), np.asarray(one_s))


def test_dequant_stats_two_level_bitwise():
    p = jnp.asarray(RNG.integers(-127, 127, size=(11, 3000)), jnp.int8)
    m = jnp.asarray(RNG.random(11).astype(np.float32))
    base_d, base_s = ops.dequant_stats(p, m, d_tile=512, macro_tile=512)
    dd, ss = ops.dequant_stats(p, m, d_tile=512, macro_tile=2048)
    np.testing.assert_array_equal(np.asarray(dd), np.asarray(base_d))
    np.testing.assert_array_equal(np.asarray(ss), np.asarray(base_s))


# ------------------------------------------------------- rectangular stats
def test_pairwise_stats_rect_matches_square_rows():
    """The §10 shard kernel: each row block of the rect kernel must be
    bitwise-identical to the matching rows of the square kernel (same
    inner tile policy + row-subset gemm determinism)."""
    x = jnp.asarray(RNG.normal(size=(13, 3000)).astype(np.float32))
    dd, sq = ops.pairwise_stats(x)
    for start, stop in ((0, 4), (4, 9), (9, 13)):
        rdd, rsq = ops.pairwise_stats_rect(x[start:stop], x)
        assert rdd.shape == (stop - start, 13) and rsq.shape == (13,)
        np.testing.assert_array_equal(np.asarray(rdd),
                                      np.asarray(dd)[start:stop])
        np.testing.assert_array_equal(np.asarray(rsq), np.asarray(sq))


def test_dequant_stats_rect_matches_square_rows():
    p = jnp.asarray(RNG.integers(-127, 127, size=(11, 2300)), jnp.int8)
    m = jnp.asarray(RNG.random(11).astype(np.float32))
    dd, sq = ops.dequant_stats(p, m)
    rdd, rsq = ops.dequant_stats_rect(p[3:8], m[3:8], p, m)
    np.testing.assert_array_equal(np.asarray(rdd), np.asarray(dd)[3:8])
    np.testing.assert_array_equal(np.asarray(rsq), np.asarray(sq))
    pb = jnp.asarray(RNG.normal(size=(11, 500)).astype(np.float32)
                     ).astype(jnp.bfloat16)
    mb = jnp.ones((11,), jnp.float32)
    dd2, sq2 = ops.dequant_stats(pb, mb)
    rdd2, rsq2 = ops.dequant_stats_rect(pb[:5], mb[:5], pb, mb)
    np.testing.assert_array_equal(np.asarray(rdd2), np.asarray(dd2)[:5])
    np.testing.assert_array_equal(np.asarray(rsq2), np.asarray(sq2))


def test_dequant_stats_rect_rejects_mixed_payloads():
    p8 = jnp.zeros((8, 256), jnp.int8)
    pb = jnp.zeros((8, 256), jnp.bfloat16)
    m = jnp.ones((8,), jnp.float32)
    with pytest.raises(ValueError):
        ops.dequant_stats_rect(p8[:4], m[:4], pb, m)


# ---------------------------------------------------------------- autotuner
def test_autotune_d_tile_lane_aligned_and_budgeted():
    for rows in (8, 24, 64, 200):
        for d in (1, 100, 4096, 10_000_000):
            t = ops.autotune_d_tile(rows, d)
            assert t % 128 == 0 and t >= 128
            # padded-d cap: never wider than the lane-rounded operand
            assert t <= max(128, ((d - 1) // 128 + 1) * 128)
            if t > 128:  # above the floor the working set obeys the budget
                assert 2 * rows * t * 4 <= ops.VMEM_BUDGET_BYTES


def test_autotune_d_tile_monotone_in_rows():
    wide = ops.autotune_d_tile(8, 10_000_000)
    narrow = ops.autotune_d_tile(512, 10_000_000)
    assert narrow <= wide
    with pytest.raises(ValueError):
        ops.autotune_d_tile(0, 128)


def test_two_level_tiles_aligned_budgeted_and_never_deeper():
    for rows, d in ((16, 257), (16, 100_000), (16, 1_000_000),
                    (64, 500_000)):
        dt, macro = ops.two_level_tiles(rows, d, out_rows=1,
                                        scratch_rows=100, fixed_bytes=4096)
        assert dt % 128 == 0 and macro % dt == 0
        if (dt, macro) != (128, 128):   # above the degenerate floor
            assert (2 * (rows + 1) * 4 * macro + (100 + rows) * 4 * dt
                    + 4096) <= ops.VMEM_BUDGET_BYTES
        # the whole point: never more outer steps than single-level
        assert -(-d // macro) <= -(-d // dt)
        # never wider than the padded operand
        assert macro <= ((d - 1) // dt + 1) * dt


def test_two_level_tiles_deep_launch_is_macro_resident():
    # the d=1e6 launch runs a multi-window macro block with a wide inner
    # window (the _MIN_D_TILE floor: tiny windows lose to loop overhead)
    dt, macro = ops.fused_select_tiles(16, 1_000_000, 7)
    assert dt >= ops._MIN_D_TILE
    assert macro >= 4 * dt
    # stats keep their PR-2 inner tile and only grow the macro block
    sdt, smacro = ops._stats_tiles(16, 1_000_000)
    assert sdt == ops.autotune_d_tile(16, 1_000_000,
                                      fixed_bytes=16 * 24 * 4)
    assert smacro > sdt and smacro % sdt == 0


def test_ops_interpret_resolved_outside_jit(monkeypatch):
    """Regression for the trace-time-baking bug: the backend/override must
    be resolved in the unjitted wrapper and reach the kernel as a static
    argument — not be re-evaluated (and cached) inside the trace."""
    seen = []
    real = ops.pairwise_sqdist_pallas

    def spy(x, *, d_tile, interpret):
        seen.append(interpret)
        return real(x, d_tile=d_tile, interpret=True)  # CPU can only interpret

    monkeypatch.setattr(ops, "pairwise_sqdist_pallas", spy)
    x = jnp.asarray(RNG.normal(size=(5, 133)).astype(np.float32))
    # unique d_tile values force fresh traces through the spy
    ops.pairwise_sqdist(x, d_tile=256, interpret=False)
    ops.pairwise_sqdist(x, d_tile=384)                # default: CPU backend
    assert seen == [False, True]


@pytest.mark.parametrize("backend,interpret", [("tpu", False), ("cpu", True)])
def test_interpret_only_on_cpu(monkeypatch, backend, interpret):
    import jax
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert ops._interpret() is interpret


def test_other_backends_never_fall_back_to_the_interpreter(monkeypatch):
    import jax
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="not on 'gpu'"):
        ops._interpret()
