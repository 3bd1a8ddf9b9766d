"""Two-level hierarchical aggregation pipeline (DESIGN.md §11).

``hier_aggregate_tree`` is the grouped counterpart of
``core.api.aggregate_tree``: per-group stats → per-group plan → per-group
apply, then the same three phases once more over the ``(n_groups, ...)``
group-aggregate stack.  Everything inside each level is the *existing*
machinery — the registry rules, the fused Pallas select kernels, the
``repro.comm`` codecs — composed, not reimplemented:

* statistics never touch an (n, n) matrix — only ceil(n/g) independent
  (≤g, ≤g) matrices plus one (n_groups, n_groups) matrix, the O(n·g)
  claim ``benchmarks/hier_scale.py`` measures;
* an :class:`~repro.comm.codecs.EncodedGrads` input is sliced per group
  (``comm.codecs.slice_workers``) so group stats run on the quantized
  payloads and the fp32 stack only ever materialises one group at a time;
* with ``codec`` set, the group aggregates are re-encoded for the
  leaders→server hop (its exact byte count is returned in ``info``) and
  decoded server-side before the outer phase — the quantization the real
  two-hop wire would cost is in the aggregate, not just accounted.

The single-group case (g >= n) short-circuits the outer level entirely:
stats/plan/apply run once over rows [0, n), which is bitwise-identical to
the flat path (tests/test_hier.py).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import api
from repro.hier.plan import GroupConfig, HierPlan

PyTree = Any

#: fold_in tag for the leaders→server re-encode key — disjoint from the
#: trainer's reserved folds (2^31-1 transforms, 2^31-2 worker encode) and
#: from any per-leaf offset a model could reach
LEADER_ENCODE_FOLD = (1 << 31) - 3


def _slice_tree(grads: PyTree, start: int, stop: int) -> PyTree:
    return jax.tree.map(lambda x: x[start:stop], grads)


def _stack_parts(parts) -> PyTree:
    return jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *parts)


def hier_aggregate_tree(grads: PyTree, f: int, cfg: GroupConfig, *,
                        codec: Optional[Any] = None,
                        key: Optional[jax.Array] = None,
                        coord_chunk: int = 0, use_pallas: bool = False,
                        fused: "bool | str" = True,
                        needs_dists: Optional[bool] = None,
                        obs: Optional[Any] = None,
                        obs_state: Optional[Dict[str, Any]] = None,
                        obs_round=None,
                        ) -> Tuple[PyTree, HierPlan, Dict[str, Any]]:
    """Aggregate a stacked pytree (or wire container) hierarchically.

    Returns ``(aggregate, HierPlan, info)`` where ``info`` carries what
    the trainers need beyond the plan: ``inner_stats`` (per-group
    :class:`AggStats`, for score diagnostics), ``outer_stats`` and
    ``leader_wire_bytes`` — the exact leaders→server byte count when
    ``codec`` is set (0 otherwise; the workers→leaders bytes live on the
    input container itself).

    ``cfg.budget(n, f)`` gates every level through
    ``core.theory.check_level`` and — unless ``cfg.enforce_budget`` is
    off — rejects budgets that do not cover the contract ``f``.
    ``codec`` (spec string or instance) re-encodes the group-aggregate
    stack for the second hop; error-feedback codecs are rejected (the
    leader hop has no persistent residual slot).  ``needs_dists=True``
    forces per-group distance matrices even for distance-free rules (the
    trainers' telemetry wants the score spectrum regardless of rule).

    ``obs``/``obs_state``/``obs_round`` thread the trainers' span ring
    (DESIGN.md §14) through the tree: with an enabled+tracing
    ``repro.obs.ObsConfig`` each level records its stats/plan/apply spans
    (payload = group count of the level) and the updated carry is
    returned as ``info["obs_state"]`` — otherwise ``obs_state`` passes
    through untouched.
    """
    from repro import obs as OBS
    obs_trace = (OBS.obs_on(obs) and obs.trace and obs_state is not None
                 and obs_state.get("t") is not None)

    def span(st, phase, payload):
        if not obs_trace:
            return st
        rnd = 0 if obs_round is None else obs_round
        return {**st, "t": OBS.record(st["t"], phase, rnd, payload)}

    enc = api._as_encoded(grads)
    if enc is not None:
        n = enc.n
    else:
        leaves = jax.tree.leaves(grads)
        if not leaves:
            raise ValueError("empty gradient pytree")
        n = leaves[0].shape[0]
    budget = cfg.budget(n, f)
    inner = api.get_aggregator(cfg.rule)
    inner_dists = inner.needs_dists if needs_dists is None else \
        (inner.needs_dists or needs_dists)

    if enc is not None:
        from repro.comm import codecs as CC
        slice_group = lambda s, e: CC.slice_workers(enc, s, e)  # noqa: E731
    else:
        slice_group = lambda s, e: _slice_tree(grads, s, e)     # noqa: E731

    inner_plans, inner_stats, parts = [], [], []
    for start, stop in budget.bounds():
        sub = slice_group(start, stop)
        st = api.compute_stats(sub, budget.f_inner,
                               needs_dists=inner_dists,
                               use_pallas=use_pallas)
        inner.validate(st.n, st.f)
        p = inner.plan(st)
        parts.append(inner.apply(p, sub, coord_chunk=coord_chunk,
                                 use_pallas=use_pallas, fused=fused))
        inner_plans.append(p)
        inner_stats.append(st)

    # inner level: one span triple (payload = group count), recorded after
    # the per-group loop so it depends on every group's work in program
    # order
    obs_state = span(obs_state, OBS.PH_STATS, budget.n_groups)
    obs_state = span(obs_state, OBS.PH_PLAN, budget.n_groups)
    obs_state = span(obs_state, OBS.PH_APPLY, budget.n_groups)

    info: Dict[str, Any] = {"inner_stats": tuple(inner_stats),
                            "outer_stats": None, "leader_wire_bytes": 0,
                            "obs_state": obs_state}
    if budget.n_groups == 1:
        # g >= n degenerates to the flat rule — no outer level, no second
        # wire hop; the single inner pass above is bitwise the flat path
        hplan = HierPlan(inner=tuple(inner_plans), outer=None, n=n, f=f,
                         g=cfg.g, bounds=budget.bounds(),
                         f_inner=budget.f_inner, f_outer=0,
                         rule=cfg.rule, outer_rule=cfg.rule)
        return parts[0], hplan, info

    inter = _stack_parts(parts)                   # (n_groups, ...) only
    if codec is not None:
        from repro.comm import codecs as CC
        c = CC.get_codec(codec) if isinstance(codec, str) else codec
        if c.stateful:
            raise ValueError(
                "hier leader re-encode does not support error-feedback "
                "codecs (no residual slot at the leader hop); drop ef=1 "
                "or aggregate without hier")
        k2 = None if key is None else \
            jax.random.fold_in(key, LEADER_ENCODE_FOLD)
        with OBS.scope("attack"):                 # the codec's wire
            enc2, _ = c.encode(inter, key=k2)
            inter = c.decode(enc2)
        info["leader_wire_bytes"] = enc2.wire_bytes

    outer_name = cfg.resolve_outer_rule(budget)
    outer = api.get_aggregator(outer_name)
    ost = api.compute_stats(inter, budget.f_outer,
                            needs_dists=outer.needs_dists,
                            use_pallas=use_pallas)
    outer.validate(ost.n, ost.f)
    op = outer.plan(ost)
    agg = outer.apply(op, inter, coord_chunk=coord_chunk,
                      use_pallas=use_pallas, fused=fused)
    # outer level: a second triple over the (n_groups, ...) stack
    # (payload = 1 marks the single outer group)
    obs_state = span(obs_state, OBS.PH_STATS, 1)
    obs_state = span(obs_state, OBS.PH_PLAN, 1)
    obs_state = span(obs_state, OBS.PH_APPLY, 1)
    info["obs_state"] = obs_state
    info["outer_stats"] = ost
    hplan = HierPlan(inner=tuple(inner_plans), outer=op, n=n, f=f,
                     g=cfg.g, bounds=budget.bounds(),
                     f_inner=budget.f_inner, f_outer=budget.f_outer,
                     rule=cfg.rule, outer_rule=outer_name)
    return agg, hplan, info
