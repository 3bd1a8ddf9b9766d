"""Streaming Multi-Bulyan: per-block backward passes, plan reuse (DESIGN.md §5).

The stacked trainer materialises the full n×d gradient stack at once —
impossible at 398B scale.  The streaming trainer exploits the plan/apply
split: the *plan* needs only the (n, n) distance matrix, which is a sum of
per-leaf contributions and can therefore be accumulated block by block
without ever holding more than one block's worker gradients; the *apply*
phase is per-leaf anyway.  Two scopes:

* ``scope="global"`` — exact Algorithm 1: pass 1 walks the parameter blocks
  accumulating the global distance matrix (gradients discarded per block),
  the plan is computed once, pass 2 re-walks the blocks applying it.  Two
  backward passes, peak gradient memory n·d/n_blocks, bit-close to the
  stacked trainer (property-tested in tests/test_trainer.py).
* ``scope="block"`` — one pass: each block computes its own distances, plan
  and aggregate.  Half the compute, but selection is per-block (a byzantine
  worker can win in one block and lose in another) — the robustness
  guarantee degrades gracefully to per-block resilience.

Blocks are the top-level entries of the parameter pytree (embed / groups /
final_norm / lm_head for the decoder-only stack).  Per-block gradients are
taken wrt the block subtree with the rest of the parameters closed over, so
each value equals the corresponding slice of the full gradient.

Phases trace under the stacked trainer's ``robust.*`` scopes
(``repro.obs.scope``); pass 1's distance accumulation is ``robust.stats``.

With ``rcfg.use_pallas`` both trainers ride the fused kernel stack: block
statistics come from the single-pass ``pairwise_stats`` kernel (one HBM
read per leaf for distances + norms) and the bulyan apply runs entirely in
VMEM via ``fused_select`` — see DESIGN.md §7 for the fused-apply contract.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, RobustConfig
from repro.core import api
from repro.dist.trainer import (_derive_mesh_ctx, _resolve_codec,
                                as_trainer_state, honest_dev_accumulate,
                                honest_dev_finalize, inject_byzantine,
                                inject_wire)
from repro import models as MD
from repro import obs as OBS
from repro.optim.optimizers import Optimizer

PyTree = Any


def _block_keys(params: PyTree):
    """Top-level block names in the full tree's leaf order.

    ``jax.tree.leaves`` iterates dict keys sorted, so walking sorted
    top-level keys and concatenating each subtree's leaves reproduces the
    global leaf order — which keeps per-leaf attack keys identical to the
    stacked trainer's.
    """
    if not isinstance(params, dict):
        return None  # degenerate: single block = the whole tree
    return sorted(params.keys())


def make_streaming_train_step(cfg: ArchConfig, rcfg: RobustConfig,
                              opt: Optimizer, lr_fn, *,
                              scope: str = "block", window: int = 0,
                              chunk_q: int = 1024, attack: str = "none",
                              attack_f: Optional[int] = None,
                              codec: Optional[str] = None,
                              coord_chunk: int = 0, telemetry: bool = False,
                              transforms: Sequence[api.Transform] = (),
                              boundary_spec=None, dx_spec=None,
                              shard_map_mesh=None, shard_map_axes=None,
                              spmd: Optional[bool] = None,
                              hier=None,
                              obs: Optional[OBS.ObsConfig] = None):
    """Build the streaming-trainer step function (same signature as stacked).

    ``attack`` accepts the same spec strings as the stacked trainer
    (``"little_is_enough:z=2.0"``); adaptive attacks are rejected — their
    plan feedback needs the full-stack step structure.  ``attack_f``
    (default ``rcfg.f``) is the number of rows the attack controls.

    ``codec`` puts the compressed wire (``repro.comm``) between workers and
    aggregator *per block*: each block's stack is encoded with the global
    leaf-offset key convention, so the wire payloads — and any wire attack
    on them — are identical to the stacked trainer's; pass-1 statistics
    accumulate straight off the quantized payloads (fused dequantize→stats
    under ``rcfg.use_pallas``).  Error-feedback codecs (``ef=1``) are
    rejected — their residual needs the stacked trainer's state slot.

    With ``telemetry`` the metrics gain the same ``"telemetry"`` sub-dict as
    the stacked trainer; under ``scope="block"`` the plan diagnostics are
    averaged over block plans (selection is per-block there — exactly the
    degradation the diagnostics exist to show).

    ``dx_spec`` (a PartitionSpec for the per-block stacked gradients) is
    accepted for the dry-run builder's mesh plumbing; it only matters when
    lowering on a production mesh.

    ``shard_map_mesh``/``shard_map_axes``/``spmd`` mirror the stacked
    trainer (DESIGN.md §10): pass-1 statistics accumulate each block's
    row-block contributions inside a shard_map over the worker axes, and
    the apply phase shards d over the model axis.

    ``hier`` (a ``repro.hier.GroupConfig``) runs the two-level grouped
    aggregation (DESIGN.md §11).  Under ``scope="global"`` pass 1
    accumulates ceil(n/g) per-group distance matrices block by block —
    never an (n, n) one — pass 2 applies the inner plans per group and
    stores only the ``(n_groups, ...)`` intermediates, and the outer
    phase runs once over those; ``scope="block"`` runs the full two-level
    pipeline per block.  Not composable with the mesh-native path.  The step takes and
    returns a :class:`~repro.dist.trainer.TrainerState` (only the ``opt``
    slot is live — a state carrying transform/attack/residual extras is
    rejected at trace time, since this trainer would silently never
    update them); a bare ``OptState`` is coerced on entry.

    ``obs`` mirrors the stacked trainer (DESIGN.md §14): an enabled
    ``repro.obs.ObsConfig`` threads the device-resident registry through
    ``TrainerState.mstate`` (the one extra slot this trainer *does*
    carry) and records stats→plan→apply spans per step; disabled/None
    compiles to the bitwise uninstrumented jaxpr.
    """
    if scope not in ("block", "global"):
        raise ValueError(f"scope must be 'block' or 'global', got {scope!r}")
    if transforms:
        raise NotImplementedError(
            "pre-aggregation transforms need the full stack; use the "
            "stacked trainer (dist.make_train_step) with transforms")
    from repro.core import attacks as ATK
    wire = isinstance(attack, str) and ATK.is_wire_attack(attack)
    if not wire and isinstance(attack, str) and ATK.is_adaptive(attack):
        raise NotImplementedError(
            "adaptive attacks need the stacked trainer's plan-feedback "
            "state; use dist.make_train_step")
    del dx_spec
    rcfg.validate()
    aggregator = api.get_aggregator(rcfg.gar)
    f_eff = rcfg.f if attack_f is None else attack_f
    if not 0 <= f_eff <= rcfg.f:
        raise ValueError(
            f"attack_f must be in [0, f] (attack_f={f_eff}, f={rcfg.f})")
    codec_obj = _resolve_codec(codec)
    if wire and codec_obj is None:
        raise ValueError(
            f"wire attack {attack!r} needs a codec= wire to attack")
    if codec_obj is not None and codec_obj.stateful:
        raise NotImplementedError(
            "error-feedback codecs carry a per-worker residual; use the "
            "stacked trainer (dist.make_train_step) with codec")
    mesh_ctx = _derive_mesh_ctx(shard_map_mesh, shard_map_axes, spmd)
    hier_budget = inner_agg = outer_agg = None
    if hier is not None:
        if mesh_ctx is not None:
            raise NotImplementedError(
                "hier= is not composable with the mesh-native (spmd) "
                "aggregation path yet; drop shard_map_mesh/spmd")
        # budget checked once at build time — rcfg.n_workers is the worker
        # count every block's stack will carry
        hier_budget = hier.budget(rcfg.n_workers, rcfg.f)
        inner_agg = api.get_aggregator(hier.rule)
        outer_agg = api.get_aggregator(hier.resolve_outer_rule(hier_budget))

    def worker_loss(p, wb):
        return MD.loss_fn(p, cfg, wb, window=window, chunk_q=chunk_q,
                          boundary_spec=boundary_spec)

    obs_live = OBS.obs_on(obs)
    obs_trace = obs_live and obs.trace

    def step(params, state, batch, key):
        state = as_trainer_state(state)
        if state.tstates or state.astate is not None \
                or state.cres is not None:
            raise NotImplementedError(
                "the streaming trainer carries only the opt slot; a "
                "TrainerState with live tstates/astate/cres belongs to "
                "the stacked trainer (dist.make_train_step)")
        opt_state = state.opt
        mstate = state.mstate
        if obs_live and mstate is None:
            mstate = OBS.init_train_obs(obs, rcfg.n_workers,
                                        telemetry=telemetry)
        obs_round = opt_state.step
        block_keys = _block_keys(params)

        def block_grads(p, k, with_loss=False):
            """Per-worker grads wrt block k of p (others closed over)."""
            if k is None:
                vg = jax.value_and_grad(worker_loss)
                with OBS.scope("workers"):
                    out = jax.vmap(lambda wb: vg(p, wb))(batch)
                return out if with_loss else out[1]

            def loss_of(bp, wb):
                q = dict(p)
                q[k] = bp
                return worker_loss(q, wb)

            vg = jax.value_and_grad(loss_of)
            with OBS.scope("workers"):
                out = jax.vmap(lambda wb: vg(p[k], wb))(batch)
            return out if with_loss else out[1]

        blocks = [None] if block_keys is None else block_keys
        # global leaf offsets so attack randomness matches the stacked path
        offsets, off = {}, 0
        for k in blocks:
            sub = params if k is None else params[k]
            offsets[k] = off
            off += len(jax.tree.leaves(sub))

        def wire_block(g, off):
            """Injection + the simulated wire for one block's stack.

            Returns ``(enc, decoded)`` — ``enc`` is None without a codec.
            Encode keys use the global-leaf-offset convention, so payloads
            (and wire-attack randomness) match the stacked trainer's
            bit for bit.
            """
            with OBS.scope("attack"):
                if not wire:
                    g = inject_byzantine(g, f_eff, attack, key,
                                         leaf_offset=off)
                if codec_obj is None:
                    return None, g
                ekey = jax.random.fold_in(key, 2 ** 31 - 2)
                enc, _ = codec_obj.encode(g, key=ekey, leaf_offset=off)
                if wire:
                    enc = inject_wire(enc, f_eff, attack, key,
                                      leaf_offset=off)
                return enc, codec_obj.decode(enc)

        plan = None
        global_diag = None
        hier_inner_plans = hier_inner_stats = None
        if hier is not None and scope == "global":
            bounds = hier_budget.bounds()
            if inner_agg.needs_dists or telemetry:
                # pass 1, grouped: accumulate ceil(n/g) per-group distance
                # matrices block by block — the (n, n) matrix never exists.
                # Per-group accumulation is leaf-by-leaf in global leaf
                # order, and each entry is a full-d reduction over one row
                # pair, so slicing rows before contracting reproduces the
                # stacked hier path's float sums exactly.
                totals = [jnp.zeros((e - s, e - s), jnp.float32)
                          for s, e in bounds]
                for k in blocks:
                    enc, g = wire_block(block_grads(params, k), offsets[k])
                    with OBS.scope("stats"):
                        if enc is not None:
                            from repro.comm import codecs as CC
                            for gi, (s, e) in enumerate(bounds):
                                totals[gi] = totals[gi] + \
                                    api.raw_pairwise_stats(
                                        CC.slice_workers(enc, s, e),
                                        use_pallas=rcfg.use_pallas)[0]
                        else:
                            for leaf in jax.tree.leaves(g):
                                for gi, (s, e) in enumerate(bounds):
                                    totals[gi] = totals[gi] + \
                                        api.raw_pairwise_stats(
                                            leaf[s:e],
                                            use_pallas=rcfg.use_pallas)[0]
                with OBS.scope("stats"):
                    hier_inner_stats = tuple(
                        api.AggStats(n=e - s, f=hier_budget.f_inner,
                                     dists=api.finalize_dists(t))
                        for (s, e), t in zip(bounds, totals))
            else:
                hier_inner_stats = tuple(
                    api.AggStats(n=e - s, f=hier_budget.f_inner)
                    for s, e in bounds)
            plans = []
            for st in hier_inner_stats:
                inner_agg.validate(st.n, st.f)
                plans.append(inner_agg.plan(st))
            hier_inner_plans = tuple(plans)
            if inner_agg.needs_dists or telemetry:
                # same CSE barrier as the flat global scope: pass 2 must
                # not keep pass 1's block gradients live
                params, hier_inner_plans = jax.lax.optimization_barrier(
                    (params, hier_inner_plans))
        elif scope == "global" and (aggregator.needs_dists or telemetry):
            # pass 1: accumulate the global (n, n) matrix block by block;
            # raw per-leaf contributions in global leaf order, finalised
            # once — the identical float summation the stacked path does.
            # (telemetry also routes distance-free rules through here: the
            # score spectrum is part of the campaign trace schema.)
            total = jnp.zeros((rcfg.n_workers, rcfg.n_workers), jnp.float32)
            for k in blocks:
                enc, g = wire_block(block_grads(params, k), offsets[k])
                with OBS.scope("stats"):
                    if enc is not None:
                        total = total + api.raw_pairwise_stats(
                            enc, use_pallas=rcfg.use_pallas,
                            mesh_ctx=mesh_ctx)[0]
                        continue
                    # leaf-by-leaf into the running total: one flat left-to-
                    # right float accumulation across ALL blocks' leaves,
                    # the exact summation order of the stacked single pass
                    # — grouping per block would reassociate the (n, n)
                    # sums by up to ~last-ulp·leaves, enough to flip
                    # near-tied scores
                    for leaf in jax.tree.leaves(g):
                        total = total + api.raw_pairwise_stats(
                            leaf, use_pallas=rcfg.use_pallas,
                            mesh_ctx=mesh_ctx)[0]
            with OBS.scope("stats"):
                stats = api.AggStats(n=rcfg.n_workers, f=rcfg.f,
                                     dists=api.finalize_dists(total))
            aggregator.validate(stats.n, stats.f)
            plan = aggregator.plan(stats)
            if telemetry:
                with OBS.scope("update"):
                    global_diag = plan.diagnostics(stats)
            # The barrier is what makes this a *streaming* trainer once
            # compiled: pass-2 recomputes byte-identical per-block gradient
            # subgraphs, and without it XLA CSE would dedupe them against
            # pass 1, keeping every block's gradients live across the plan
            # computation — silently restoring the n·d peak the two-pass
            # structure exists to avoid.  Tying params through the barrier
            # with the plan makes pass 2 depend on pass 1's completion.
            params, plan = jax.lax.optimization_barrier((params, plan))
        elif hier is None and not aggregator.needs_dists:
            # distance-free rules: the plan is block-independent
            stats = api.AggStats(n=rcfg.n_workers, f=rcfg.f)
            aggregator.validate(stats.n, stats.f)
            plan = aggregator.plan(stats)

        if obs_trace:
            # one span per phase per step — pass-1 stats + the (global or
            # per-block) plan; payload marks whether a global plan exists
            t = OBS.record(mstate["t"], OBS.PH_STATS, obs_round)
            t = OBS.record(t, OBS.PH_PLAN, obs_round,
                           0.0 if plan is None else 1.0)
            mstate = {**mstate, "t": t}

        # pass 2 (or the only pass): aggregate block by block; the first
        # block's value_and_grad also yields the per-worker loss metrics
        agg_blocks = {}
        inter_blocks = {}
        hm_blocks = {}
        losses = None
        block_diags = []
        wire_total = 0
        leader_total = 0
        dev_sq = jnp.zeros((), jnp.float32)
        ref_sq = jnp.zeros((), jnp.float32)
        for k in blocks:
            if losses is None:
                losses, g = block_grads(params, k, with_loss=True)
            else:
                g = block_grads(params, k)
            enc, g = wire_block(g, offsets[k])
            if enc is not None:
                wire_total += enc.wire_bytes
            if hier is not None and scope == "block":
                # the full two-level pipeline per block (selection is per
                # block AND per group — the documented scope degradation)
                from repro.hier import hier_aggregate_tree
                agg_k, hplan_k, hinfo_k = hier_aggregate_tree(
                    enc if enc is not None else g, rcfg.f, hier,
                    codec=codec_obj, key=key, coord_chunk=coord_chunk,
                    use_pallas=rcfg.use_pallas,
                    needs_dists=True if telemetry else None)
                agg_blocks[k] = agg_k
                leader_total += hinfo_k["leader_wire_bytes"]
                if telemetry:
                    with OBS.scope("update"):
                        block_diags.append(
                            hplan_k.diagnostics(hinfo_k["inner_stats"]))
                        dev_sq, ref_sq = honest_dev_accumulate(
                            dev_sq, ref_sq, agg_k, g, f_eff)
                continue
            if hier is not None:
                # scope == "global": apply the global inner plans per
                # group; only the (n_groups, ...) intermediate survives
                # the block — the worker-axis stack is dropped with g
                parts = [
                    inner_agg.apply(
                        pg, jax.tree.map(lambda x: x[s:e], g),
                        coord_chunk=coord_chunk,
                        use_pallas=rcfg.use_pallas)
                    for pg, (s, e) in zip(hier_inner_plans,
                                          hier_budget.bounds())]
                inter_blocks[k] = jax.tree.map(
                    lambda *xs: jnp.stack(xs, axis=0), *parts)
                if telemetry:
                    # honest means are d-sized — keep them for the
                    # deviation once the outer aggregate exists
                    with OBS.scope("update"):
                        hm_blocks[k] = jax.tree.map(
                            lambda x: jnp.mean(
                                x[f_eff:].astype(jnp.float32), axis=0), g)
                continue
            block_plan = plan
            if block_plan is None or (telemetry and scope == "block"):
                stats_k = api.compute_stats(
                    enc if enc is not None else g, rcfg.f,
                    needs_dists=True, use_pallas=rcfg.use_pallas,
                    mesh_ctx=mesh_ctx)
                if block_plan is None:  # scope == "block", distance rule
                    aggregator.validate(stats_k.n, stats_k.f)
                    block_plan = aggregator.plan(stats_k)
                if telemetry:
                    with OBS.scope("update"):
                        block_diags.append(block_plan.diagnostics(stats_k))
            agg_blocks[k] = aggregator.apply(
                block_plan, g, coord_chunk=coord_chunk,
                use_pallas=rcfg.use_pallas, mesh_ctx=mesh_ctx)
            if telemetry:
                with OBS.scope("update"):
                    dev_sq, ref_sq = honest_dev_accumulate(
                        dev_sq, ref_sq, agg_blocks[k], g, f_eff)

        if hier is not None and scope == "global":
            # outer phase, once, over the stored (n_groups, ...) stack
            inter = inter_blocks[None] if block_keys is None else \
                {k: inter_blocks[k] for k in block_keys}
            outer_plan = None
            if hier_budget.n_groups == 1:
                agg = jax.tree.map(lambda x: x[0], inter)
            else:
                if codec_obj is not None:
                    from repro.hier import LEADER_ENCODE_FOLD
                    k2 = jax.random.fold_in(key, LEADER_ENCODE_FOLD)
                    with OBS.scope("attack"):
                        enc2, _ = codec_obj.encode(inter, key=k2)
                        inter = codec_obj.decode(enc2)
                    leader_total += enc2.wire_bytes
                ost = api.compute_stats(
                    inter, hier_budget.f_outer,
                    needs_dists=outer_agg.needs_dists or telemetry,
                    use_pallas=rcfg.use_pallas)
                outer_agg.validate(ost.n, ost.f)
                outer_plan = outer_agg.plan(ost)
                agg = outer_agg.apply(outer_plan, inter,
                                      coord_chunk=coord_chunk,
                                      use_pallas=rcfg.use_pallas)
            if telemetry:
                with OBS.scope("update"):
                    from repro.hier import HierPlan
                    hplan = HierPlan(
                        inner=hier_inner_plans, outer=outer_plan,
                        n=rcfg.n_workers, f=rcfg.f, g=hier.g,
                        bounds=hier_budget.bounds(),
                        f_inner=hier_budget.f_inner,
                        f_outer=hier_budget.f_outer, rule=hier.rule,
                        outer_rule=hier.resolve_outer_rule(hier_budget))
                    global_diag = hplan.diagnostics(hier_inner_stats)
                    hm = hm_blocks[None] if block_keys is None else \
                        {k: hm_blocks[k] for k in block_keys}
                    for a, m in zip(jax.tree.leaves(agg), jax.tree.leaves(hm)):
                        dev_sq = dev_sq + jnp.sum(
                            (a.astype(jnp.float32) - m) ** 2)
                        ref_sq = ref_sq + jnp.sum(m ** 2)
        elif block_keys is None:
            agg = agg_blocks[None]
        else:
            agg = {k: agg_blocks[k] for k in block_keys}

        with OBS.scope("update"):
            lr = lr_fn(opt_state.step)
            new_params, new_opt = opt.update(agg, opt_state, params, lr)
            gnorm = jnp.sqrt(sum(jnp.sum(g.astype(jnp.float32) ** 2)
                                 for g in jax.tree.leaves(agg)))
            metrics = {
                "loss": jnp.mean(losses),
                "loss_per_worker": losses,
                "lr": jnp.asarray(lr, jnp.float32),
                "agg_grad_norm": gnorm,
            }
            if telemetry:
                if global_diag is not None:
                    diag = dict(global_diag)
                else:
                    # scope == "block": selection is per-block; report the
                    # mean over block plans (the per-block degradation is
                    # the point)
                    diag = {kk: jnp.mean(jnp.stack(
                        [d[kk] for d in block_diags]), axis=0)
                        for kk in block_diags[0]}
                # captured mass over the rows the attack actually holds
                # (f_eff)
                diag["byz_mass"] = jnp.sum(diag["selection"][:f_eff])
                diag["honest_dev"] = honest_dev_finalize(dev_sq, ref_sq)
                if codec_obj is not None:
                    diag["wire_bytes_per_worker"] = jnp.asarray(
                        wire_total / rcfg.n_workers, jnp.float32)
                if hier is not None and codec_obj is not None:
                    diag["leader_wire_bytes"] = jnp.asarray(
                        leader_total, jnp.float32)
                metrics["telemetry"] = diag
            if obs_live:
                m = mstate["m"]
                m = OBS.inc(m, "rounds")
                m = OBS.set_gauge(m, "loss", metrics["loss"])
                m = OBS.set_gauge(m, "agg_grad_norm", gnorm)
                m = OBS.observe(m, "agg_grad_norm", gnorm)
                if telemetry:
                    m = OBS.set_gauge(m, "byz_mass", diag["byz_mass"])
                    m = OBS.set_gauge(m, "suspicion", OBS.update_suspicion(
                        m.gauges["suspicion"], diag["selection"],
                        obs.suspicion_ema))
                t = mstate["t"]
                if obs_trace:
                    t = OBS.record(t, OBS.PH_APPLY, obs_round, gnorm)
                mstate = {"m": m, "t": t}
        return (new_params,
                dataclasses.replace(state, opt=new_opt, mstate=mstate),
                metrics)

    return step
