"""repro.analysis: lint rules, jaxpr contract auditors, VMEM estimator.

Every rule/auditor must trip on its known-bad fixture AND pass on the
real repo — a gate that is vacuous in either direction is worse than no
gate.  The VMEM estimator is held to the committed BENCH_agg_time.json
grid: it must launch on the exact two-level tile pair the kernels use
and keep the d=1e6 point macro-resident (cliff closed).
"""
import json
import os

import pytest
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.analysis import jaxpr_audit as JA
from repro.analysis import lint, vmem
from repro.core import api
from repro.launch.mesh import make_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "fixtures_analysis")
LINT_PATHS = [os.path.join(REPO, p)
              for p in ("src", "benchmarks", "examples")]

KEY = jax.random.key(0)


def _mesh11():
    """A 1×1 (data, model) mesh: tracing needs axis *names*, not devices."""
    return make_mesh((1, 1), ("data", "model"))


# ================================================================= lint
@pytest.mark.parametrize("rule", sorted(lint.RULES))
def test_lint_rule_trips_on_fixture(rule):
    path = os.path.join(FIXTURES, f"bad_{rule.lower()}.py")
    found = {v.rule for v in lint.lint_paths([path])}
    assert rule in found, f"{rule} did not fire on {path}: {found}"


def test_lint_fixture_hits_are_only_the_advertised_rule():
    # R000 shadows everything (unparseable), R001's import-time calls are
    # the only violations in its file, etc. — no rule may false-positive
    # on another rule's fixture beyond its own advertised id
    for rule in sorted(lint.RULES):
        path = os.path.join(FIXTURES, f"bad_{rule.lower()}.py")
        for v in lint.lint_paths([path]):
            assert v.rule == rule, (rule, str(v))


def test_repo_lints_clean():
    violations = lint.lint_paths(LINT_PATHS)
    assert violations == [], "\n".join(str(v) for v in violations)


def test_violation_render_and_json():
    (v,) = lint.lint_source("import jax.numpy as jnp\nX = jnp.zeros(3)\n",
                            "mod.py")
    assert v.rule == "R001" and v.line == 2
    assert "mod.py:2" in str(v)
    assert v.to_json()["rule"] == "R001"


# ========================================================== jaxpr audits
@pytest.fixture(scope="module")
def grads():
    return {"w": jax.random.normal(KEY, (11, 8, 32)),
            "b": jax.random.normal(jax.random.key(1), (11, 16))}


def test_c201_proven_on_repo_apply(grads):
    ctx = api.MeshContext.for_mesh(_mesh11())
    res = JA.audit_apply_gather(grads, f=2, mesh_ctx=ctx)
    assert res.ok, res.violations


def test_c201_trips_on_model_axis_gather():
    mesh = _mesh11()

    def body(x):
        return jax.lax.all_gather(x, ("data", "model"), axis=0, tiled=True)

    fn = jax.shard_map(body, mesh=mesh, in_specs=P("data"),
                       out_specs=P(None), check_vma=False)
    closed = jax.make_jaxpr(fn)(jnp.zeros((8, 16)))
    violations, gathers = JA.gather_violations(
        closed, allowed=10 ** 9, model_axis="model")
    assert gathers == 1 and violations, violations


def test_c201_trips_on_oversized_gather():
    mesh = _mesh11()

    def body(x):
        return jax.lax.all_gather(x, "data", axis=0, tiled=True)

    fn = jax.shard_map(body, mesh=mesh, in_specs=P("data"),
                       out_specs=P(None), check_vma=False)
    closed = jax.make_jaxpr(fn)(jnp.zeros((8, 16)))
    violations, _ = JA.gather_violations(
        closed, allowed=8 * 16 - 1, model_axis="model")
    assert violations and "exceeds" in violations[0]


def test_c202_proven_on_repo_encoded_path(grads):
    ctx = api.MeshContext.for_mesh(_mesh11())
    res = JA.audit_decode_invariant(grads, f=2, mesh_ctx=ctx)
    assert res.ok, res.violations


def test_c202_trips_on_replicated_decode():
    # the forbidden §9 shape: dequantize the full (n, d) payload at the
    # top level (outside any shard body)
    def replicated(p, m):
        return (p.astype(jnp.float32) * m[:, None]).sum(0)

    closed = jax.make_jaxpr(replicated)(
        jnp.zeros((8, 16), jnp.int8), jnp.ones((8,)))
    violations, decodes = JA.full_stack_decodes(closed, 8,
                                                require_in_shard=True)
    assert decodes == 1 and violations, violations


def test_c203_proven_on_repo_and_self_test(grads):
    ctx = api.MeshContext.for_mesh(_mesh11())
    closed = jax.make_jaxpr(
        lambda g: api.aggregate_tree(g, 2, "multi_bulyan",
                                     mesh_ctx=ctx))(grads)
    assert JA.audit_tp_seam(closed).ok
    # the self-test *is* the negative fixture: it must report "proven",
    # which certifies the auditor tripped on the synthetic tp flatten
    assert JA.tp_seam_self_test().ok


def test_c203_trips_on_constrained_flatten():
    mesh = _mesh11()

    def bad(x):
        x = jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(None, None, "model")))
        return x.reshape(x.shape[0], -1)

    closed = jax.make_jaxpr(bad)(jnp.zeros((8, 4, 64)))
    res = JA.audit_tp_seam(closed)
    assert not res.ok and "§10" in res.violations[0]


def test_c204_proven_on_jitted_aggregate(grads):
    fn = jax.jit(lambda g: api.aggregate_tree(g, 2, "multi_bulyan"))
    res = JA.audit_single_compile(fn, lambda: (grads,), label="agg")
    assert res.ok, res.violations


def test_c204_trips_on_retracing_fn():
    calls = [0]

    def make_args():
        calls[0] += 1
        return (jnp.ones((4,)), float(calls[0]))   # new static each call

    fn = jax.jit(lambda x, s: x.sum() + s, static_argnums=(1,))
    res = JA.audit_single_compile(fn, make_args, label="retracey")
    assert not res.ok and res.violations


def test_c205_proven_on_hier_path():
    grads21 = {"w": jax.random.normal(KEY, (21, 8, 32))}
    res = JA.audit_hier_decode(grads21, f=1, spec="g=7")
    assert res.ok, res.violations


def test_c205_trips_on_full_stack_decode():
    def bad(p, m):
        return (p.astype(jnp.float32) * m[:, None])[:7].mean(0)

    closed = jax.make_jaxpr(bad)(
        jnp.zeros((21, 16), jnp.int8), jnp.ones((21,)))
    violations, _ = JA.full_stack_decodes(closed, 21,
                                          require_in_shard=False)
    assert violations


# ================================================================= vmem
@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(REPO, "BENCH_agg_time.json")) as fh:
        payload = json.load(fh)
    return payload.get("results", payload)


def test_vmem_matches_tile_policy_at_grid_points():
    # the estimator must live on the exact (d_tile, macro_tile) pair the
    # wrappers launch with — the shared two-level policy, called not
    # re-derived
    from repro.kernels import ops
    for n, d in ((11, 4096), (15, 100_000), (15, 1_000_000)):
        est = vmem.estimate_fused_select(n, d)
        n_pad = n + (-n) % 8
        theta = n - 2 * vmem.f_for_bench(n) - 2
        want = ops.fused_select_tiles(n_pad, d, theta)
        assert (est.d_tile, est.macro_tile) == want
        assert est.macro_tile % est.d_tile == 0
        assert est.windows == est.macro_tile // est.d_tile
        assert est.vmem_bytes <= est.vmem_budget   # chosen pair must fit
        stats = vmem.estimate_pairwise_stats(n, d)
        assert (stats.d_tile, stats.macro_tile) == ops._stats_tiles(n_pad, d)
        assert stats.vmem_bytes <= stats.vmem_budget


def test_vmem_stats_inner_tile_is_the_pr2_autotune_value():
    # the stats inner window is bitwise-pinned to the single-level
    # autotune tile (tile boundaries ARE the accumulation order); only
    # the macro block is new
    from repro.kernels import ops
    for n, d in ((15, 100_000), (15, 1_000_000)):
        n_pad = n + (-n) % 8
        fixed = n_pad * (n_pad + 8) * 4
        est = vmem.estimate_pairwise_stats(n, d)
        assert est.d_tile == ops.autotune_d_tile(n_pad, d,
                                                 fixed_bytes=fixed)


def test_vmem_two_level_closes_the_d1e6_cliff():
    # the deep launch must tile (over_budget), fit per macro step, and
    # run a multi-window macro block that cuts the outer grid depth well
    # below the single-level d_tile grid (the retired cliff regime)
    est = vmem.estimate_fused_select(15, 1_000_000)
    assert est.over_budget and not est.tile_over_budget, est
    assert est.macro_tile > est.d_tile and est.windows > 1, est
    single_level_steps = -(-1_000_000 // est.d_tile)
    assert est.grid_steps * 4 <= single_level_steps, est
    # the residual weight re-read term is amortised over the macro block:
    # read traffic stays within 2% of one clean pass over the stack
    one_pass = 16 * est.grid_steps * est.macro_tile * 4
    assert est.hbm_read_bytes <= 1.02 * one_pass, est


def test_vmem_traffic_linearity_holds_on_committed_bench(bench):
    diag = vmem.diagnose_traffic_linearity(bench)
    assert diag["holds"], diag
    deepest = [p for p in diag["points"] if p["deepest"]]
    assert deepest, diag
    for p in deepest:
        # the deepest-d point of every n sustains >= half the peak
        # measured bytes/us of that n — cost stays linear in traffic
        assert p["throughput_vs_peak"] >= 0.5, p


def test_vmem_other_kernels_estimable():
    for kernel in ("pairwise_stats", "dequant_stats"):
        est = vmem.estimate(kernel, 15, 100_000)
        assert est.grid_steps >= 1 and est.hbm_read_bytes > 0
    bf16 = vmem.estimate_dequant_stats(15, 100_000, dtype="bfloat16")
    i8 = vmem.estimate_dequant_stats(15, 100_000, dtype="int8")
    assert bf16.hbm_read_bytes > i8.hbm_read_bytes
    with pytest.raises(ValueError):
        vmem.estimate("warp_drive", 15, 4096)
    with pytest.raises(ValueError):
        vmem.estimate_fused_select(15, 4096, d_tile=256, macro_tile=384)
