"""repro.hier — hierarchical aggregation: parity, budgets, apply kernel, sim.

The load-bearing acceptance test is *bitwise* flat parity: with g >= n the
hierarchy degenerates to a single group and must reproduce
``core.api.aggregate_tree`` exactly (same stats, same plan, same apply),
on the PR-2 edge grid (n not divisible by 8, d not divisible by 128).
"""
from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import api, theory
from repro.hier import GroupConfig, hier_aggregate_tree

KEY = jax.random.key(7)


def _tree(n: int, key=KEY):
    """Two-leaf tree on the PR-2 edge shapes (d not divisible by 128)."""
    ka, kb = jax.random.split(key)
    return {"a": jax.random.normal(ka, (n, 100), jnp.float32),
            "b": jax.random.normal(kb, (n, 257), jnp.float32)}


# ========================================================================
# f-budget arithmetic (core.theory.split_f_budget)
# ========================================================================
def test_group_sizes_balanced_contiguous():
    assert theory.group_sizes(11, 4) == (4, 4, 3)
    assert theory.group_sizes(64, 16) == (16, 16, 16, 16)
    assert theory.group_sizes(5, 8) == (5,)
    assert sum(theory.group_sizes(2048, 64)) == 2048


def test_split_f_budget_derivation():
    b = theory.split_f_budget(256, 7, 16)
    assert (b.n_groups, b.f_inner, b.f_outer) == (16, 3, 1)
    assert b.covers()
    # g >= n: single group, flat budget, no outer level
    b = theory.split_f_budget(11, 2, 11)
    assert (b.n_groups, b.f_inner, b.f_outer) == (1, 2, 0)
    assert b.bounds() == ((0, 11),)


def test_split_f_budget_rejects_infeasible_levels():
    # derived f_outer=1 but only 3 groups: bulyan outer needs 4f+3 = 7
    with pytest.raises(ValueError, match="outer.*requires n >="):
        theory.split_f_budget(12, 1, 4)
    # inner override past the group size
    with pytest.raises(ValueError, match="inner.*requires n >="):
        theory.split_f_budget(64, 7, 16, f_inner=5)


def test_split_f_budget_enforce_coverage():
    with pytest.raises(ValueError, match="does not cover contract"):
        theory.split_f_budget(21, 7, 7, f_inner=1, f_outer=0)
    b = theory.split_f_budget(21, 7, 7, f_inner=1, f_outer=0,
                              enforce=False)
    assert not b.covers()
    assert b.capturable_groups() == 3


def test_group_config_from_spec():
    gc = GroupConfig.from_spec("g=64")
    assert (gc.g, gc.rule) == (64, "multi_bulyan")
    gc = GroupConfig.from_spec(
        "g=7,rule=multi_krum,outer_rule=krum,f_inner=1,enforce=0")
    assert gc == GroupConfig(g=7, rule="multi_krum", outer_rule="krum",
                             f_inner=1, enforce_budget=False)
    with pytest.raises(ValueError, match="needs g="):
        GroupConfig.from_spec("rule=krum")
    with pytest.raises(ValueError, match="unknown --hier key"):
        GroupConfig.from_spec("g=4,zap=1")


# ========================================================================
# g >= n degenerate case: bitwise-identical to the flat rule
# ========================================================================
@pytest.mark.parametrize("rule", ["multi_bulyan", "multi_krum"])
@pytest.mark.parametrize("n,f", [(7, 1), (11, 2), (15, 3), (12, 2)])
def test_single_group_bitwise_flat(rule, n, f):
    grads = _tree(n, jax.random.fold_in(KEY, n))
    flat = api.aggregate_tree(grads, f, name=rule)
    agg, plan, info = hier_aggregate_tree(
        grads, f, GroupConfig(g=n, rule=rule))
    assert plan.outer is None and plan.n_groups == 1
    for k in flat:
        np.testing.assert_array_equal(np.asarray(flat[k]),
                                      np.asarray(agg[k]),
                                      err_msg=f"{rule} n={n} leaf {k}")
    # telemetry degenerates too: group_selection is the trivial simplex
    d = plan.diagnostics(info["inner_stats"])
    np.testing.assert_array_equal(np.asarray(d["group_selection"]), [1.0])


def test_single_group_bitwise_flat_under_jit():
    grads = _tree(11)
    flat = jax.jit(lambda g: api.aggregate_tree(g, 2, name="multi_bulyan"))(
        grads)
    hier = jax.jit(lambda g: hier_aggregate_tree(
        g, 2, GroupConfig(g=11))[0])(grads)
    for k in flat:
        np.testing.assert_array_equal(np.asarray(flat[k]),
                                      np.asarray(hier[k]))


# ========================================================================
# multi-group semantics
# ========================================================================
def test_group_permutation_invariance():
    # 7 groups of 7 with a robust outer (f_outer=1): permuting whole
    # groups permutes the outer level's inputs, which the rule is
    # invariant to
    n, f, g = 49, 3, 7
    grads = _tree(n)
    cfg = GroupConfig(g=g)
    agg, plan, _ = hier_aggregate_tree(grads, f, cfg)
    assert (plan.f_inner, plan.f_outer) == (1, 1)
    perm = np.array([3, 0, 6, 1, 5, 2, 4])
    rows = np.concatenate([np.arange(k * g, (k + 1) * g) for k in perm])
    permuted = jax.tree.map(lambda x: x[rows], grads)
    agg_p, _, _ = hier_aggregate_tree(permuted, f, cfg)
    for k in agg:
        np.testing.assert_allclose(np.asarray(agg[k]), np.asarray(agg_p[k]),
                                   rtol=2e-5, atol=2e-6)


def test_selection_weights_convex_over_workers():
    grads = _tree(49)
    _, plan, info = hier_aggregate_tree(grads, 3, GroupConfig(g=7))
    sel = np.asarray(plan.selection_weights())
    assert sel.shape == (49,)
    assert np.all(sel >= 0)
    np.testing.assert_allclose(sel.sum(), 1.0, rtol=1e-5)
    d = plan.diagnostics(info["inner_stats"])
    assert d["score_spectrum"].shape == (49,)
    assert np.asarray(d["group_selection"]).shape == (7,)


def test_poisoned_subtree_rejected_by_robust_outer():
    # all 7 traitors in group 0 (the contiguous first-rows placement);
    # inner budget deliberately under-provisioned (f_inner=1) so group 0's
    # aggregate goes byzantine — the krum outer over 7 groups must reject
    # it and route zero selection mass to group 0
    n, f, g = 49, 7, 7
    grads = _tree(n)
    grads = jax.tree.map(lambda x: x.at[:f].set(x[:f] + 50.0), grads)
    cfg = GroupConfig(g=g, f_inner=1, f_outer=1, outer_rule="krum",
                      enforce_budget=False)
    _, plan, info = hier_aggregate_tree(grads, f, cfg)
    d = plan.diagnostics(info["inner_stats"])
    gsel = np.asarray(d["group_selection"])
    assert gsel[0] == pytest.approx(0.0, abs=1e-6)
    assert float(d["byz_mass"]) == pytest.approx(0.0, abs=1e-6)


def test_poisoned_subtree_captured_without_outer_robustness():
    # same under-provisioned inner budget but an averaging outer level:
    # the captured group's full 1/n_groups mass flows through
    n, f, g = 21, 7, 7
    grads = _tree(n)
    grads = jax.tree.map(lambda x: x.at[:f].set(x[:f] + 50.0), grads)
    cfg = GroupConfig(g=g, f_inner=1, f_outer=0, enforce_budget=False)
    _, plan, _ = hier_aggregate_tree(grads, f, cfg)
    d = plan.diagnostics()
    assert float(d["byz_mass"]) == pytest.approx(1 / 3, abs=0.05)


def test_budget_rejection_through_aggregate():
    grads = _tree(21)
    with pytest.raises(ValueError, match="does not cover contract"):
        hier_aggregate_tree(grads, 7, GroupConfig(g=7, f_inner=1,
                                                  f_outer=0))


def test_encoded_input_and_leader_reencode():
    from repro.comm import get_codec
    codec = get_codec("qsgd:bits=4")
    grads = _tree(21)
    enc, _ = codec.encode(grads, key=jax.random.fold_in(KEY, 1))
    agg, plan, info = hier_aggregate_tree(
        enc, 1, GroupConfig(g=7), codec=codec,
        key=jax.random.fold_in(KEY, 2))
    assert plan.n_groups == 3
    assert 0 < info["leader_wire_bytes"] < enc.wire_bytes
    # the aggregate is the decoded two-hop pipeline's output — same shapes
    assert {k: v.shape for k, v in agg.items()} == \
        {"a": (100,), "b": (257,)}


# ========================================================================
# the Bulyan apply's kernel under use_pallas
# ========================================================================
def _spy_fused_select(monkeypatch):
    from repro.kernels import ops as kops
    calls = []
    real = kops.fused_select

    def spy(*args, **kw):
        calls.append(args[0].shape)
        return real(*args, **kw)

    monkeypatch.setattr(kops, "fused_select", spy)
    return calls


@pytest.mark.parametrize("fused", [True, "force"])
@pytest.mark.parametrize("d", [100, 1_048_577])
def test_apply_takes_fused_select_at_every_leaf_size(monkeypatch, d, fused):
    """Under ``use_pallas`` every leaf's apply runs ``fused_select``, past
    1e6 coordinates as below.  Traced only (``jax.eval_shape``): the big
    leaf never runs through the interpreted kernel."""
    calls = _spy_fused_select(monkeypatch)
    leaf = jax.ShapeDtypeStruct((11, d), jnp.float32)
    out = jax.eval_shape(lambda g: api.aggregate_tree(
        g, 2, name="multi_bulyan", use_pallas=True, fused=fused), {"w": leaf})
    assert calls == [(11, d)]
    assert out["w"].shape == (d,)


@pytest.mark.parametrize("use_pallas,fused", [(False, True), (True, False)])
def test_apply_without_fused_select(monkeypatch, use_pallas, fused):
    """The XLA substrate (``use_pallas=False``) and the two-step Pallas
    path (``fused=False``) keep their meaning: no ``fused_select``."""
    calls = _spy_fused_select(monkeypatch)
    leaf = jax.ShapeDtypeStruct((11, 1_048_577), jnp.float32)
    jax.eval_shape(lambda g: api.aggregate_tree(
        g, 2, name="multi_bulyan", use_pallas=use_pallas, fused=fused),
        {"w": leaf})
    assert calls == []


# ========================================================================
# campaign-level acceptance (sim integration)
# ========================================================================
def test_hier_campaign_poisoned_subtree():
    from repro.sim import AttackPhase, AttackSchedule, Scenario, \
        run_campaign
    sched = AttackSchedule((
        AttackPhase(steps=2, attack="none"),
        AttackPhase(steps=2, attack="little_is_enough:z=4.0")))
    sc = Scenario(name="hier-capture-test", schedule=sched, n_workers=21,
                  f=7, gar="multi_bulyan", hier_g=7, hier_f_inner=1,
                  hier_f_outer=0, hier_enforce=False, seq=32,
                  per_worker_batch=1)
    r = run_campaign(sc)
    assert r.trace["group_selection"].shape == (4, 3)
    assert r.trace["group_suspicion"].shape == (4, 3)
    # whole-group collusion through an under-provisioned inner budget:
    # group 0's full averaging share flows into the update
    assert float(np.mean(r.trace["byz_mass"][2:])) > 0.15
    ph = r.summary["phases"][1]
    assert len(ph["group_selection_mean"]) == 3
    assert len(ph["group_suspicion_last"]) == 3


def test_scenario_rejects_bad_hier():
    from repro.sim import AttackPhase, AttackSchedule, Scenario
    sched = AttackSchedule((AttackPhase(steps=1),))
    with pytest.raises(ValueError, match="does not cover contract"):
        Scenario(name="x", schedule=sched, n_workers=21, f=7, hier_g=7,
                 hier_f_inner=1, hier_f_outer=0)
    with pytest.raises(ValueError, match="error-feedback"):
        Scenario(name="x", schedule=sched, n_workers=21, f=1, hier_g=7,
                 codec="topk:frac=0.1,ef=1")
