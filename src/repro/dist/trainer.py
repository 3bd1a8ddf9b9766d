"""Stacked byzantine-SGD trainer built on the core plan/apply Aggregator API.

One train step (DESIGN.md §3):

1. forward+backward per worker (``vmap`` over the leading worker axis of the
   batch) -> stacked gradient pytree, every leaf ``(n, ...)``;
2. :func:`inject_byzantine` overwrites the first ``f`` worker rows with the
   selected attack's proposals (gradient-level omniscient adversary);
3. the optional pre-aggregation transform pipeline (worker momentum,
   clipping, nearest-neighbour mixing — ``core.api``) rewrites the stack;
4. ``Aggregator.plan`` on the replicated (n, n) statistics, then
   ``Aggregator.apply`` leaf-by-leaf (sharding-preserving einsums +
   coordinate phase);
5. one optimizer update from the aggregated gradient.

Each phase traces under its ``repro.obs`` scope, so a device profile
attributes every operation: ``robust.workers`` (1; forward ops under
``jvp(robust.workers)``, backward under ``transpose(jvp(...))``),
``robust.attack`` (2, the codec's wire included), ``robust.stats``,
``robust.plan`` and ``robust.apply`` (4), ``robust.update`` (5, with the
step's metrics).  The transforms of step 3 carry no scope of their own.

The returned step has signature ``(params, state, batch, key) ->
(params, state, metrics)`` where ``state`` is the named
:class:`TrainerState` pytree (optimizer + transform + adaptive-attack +
error-feedback slots) — seed it with :func:`init_train_state`.  A bare
``OptState`` is accepted for convenience and coerced on entry.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, RobustConfig
from repro.core import api
from repro.core import attacks as ATK
from repro import models as MD
from repro import obs as OBS
from repro.optim.optimizers import OptState, Optimizer

PyTree = Any


# --------------------------------------------------------------------- data
def split_workers(batch: PyTree, n_workers: int) -> PyTree:
    """(global_batch, ...) leaves -> (n_workers, per_worker, ...) leaves."""

    def sp(x):
        b = x.shape[0]
        if b % n_workers:
            raise ValueError(
                f"global batch {b} not divisible by n_workers={n_workers}")
        return x.reshape((n_workers, b // n_workers) + x.shape[1:])

    return jax.tree.map(sp, batch)


# ------------------------------------------------------------------ attacks
def _attack_leaf(attack_fn: ATK.Attack, leaf: jax.Array, f: int,
                 key) -> jax.Array:
    """Replace the first f worker rows of one leaf with attack proposals.

    The attack sees the (n-f, numel) stack of *correct* gradients (rows
    f..n), per the omniscient-adversary convention in ``core/attacks.py``.
    """
    correct = leaf[f:]
    flat = correct.reshape((correct.shape[0], -1)).astype(jnp.float32)
    byz = attack_fn(flat, f, key)
    byz = byz.reshape((f,) + leaf.shape[1:]).astype(leaf.dtype)
    return jnp.concatenate([byz, correct], axis=0)


def inject_byzantine(grads: PyTree, f: int, attack, key,
                     *, leaf_offset: int = 0) -> PyTree:
    """Overwrite the first ``f`` worker rows of every leaf with the attack.

    ``attack`` is an attack spec string — a bare name or ``"name:k=v,..."``
    with parameter overrides (``core.attacks.get_attack``) — or an already
    resolved ``(G, f, key) -> (f, d)`` callable (the adaptive-attack path
    passes a state-closed closure).

    Per-leaf keys are ``fold_in(key, leaf_offset + leaf_index)`` so that a
    streaming trainer processing blocks of leaves reproduces the stacked
    trainer's randomness exactly (``leaf_offset`` = the block's position in
    the full tree's leaf order).
    """
    if f == 0:
        return grads
    attack_fn = ATK.get_attack(attack) if isinstance(attack, str) else attack
    leaves, treedef = jax.tree.flatten(grads)
    out = [
        _attack_leaf(attack_fn, leaf, f,
                     jax.random.fold_in(key, leaf_offset + i))
        for i, leaf in enumerate(leaves)
    ]
    return jax.tree.unflatten(treedef, out)


def inject_wire(enc, f: int, attack, key, *, leaf_offset: int = 0):
    """Overwrite the first ``f`` workers' *wire messages* with the attack.

    The wire-format counterpart of :func:`inject_byzantine`: ``attack`` is
    a wire-attack spec (``core.attacks.WIRE_ATTACKS`` — ``scale_poison``,
    ``payload_flip``) mutating payload rows and scale sidecars of a
    ``repro.comm`` :class:`EncodedGrads` container directly, after honest
    workers encoded.  Same per-leaf key convention as gradient injection
    (``fold_in(key, leaf_offset + leaf_index)``) so streaming blocks
    reproduce the stacked randomness.
    """
    if f == 0:
        return enc
    import dataclasses as _dc
    fn = ATK.get_wire_attack(attack) if isinstance(attack, str) else attack
    p_leaves, treedef = jax.tree.flatten(enc.payload)
    s_leaves = jax.tree.leaves(enc.sidecar) \
        if enc.sidecar is not None else [None] * len(p_leaves)
    new_p, new_s = [], []
    for i, (p, s) in enumerate(zip(p_leaves, s_leaves)):
        k = jax.random.fold_in(key, leaf_offset + i)
        pb, sb = fn(p[f:], None if s is None else s[f:], f, k)
        new_p.append(jnp.concatenate([pb.astype(p.dtype), p[f:]], axis=0))
        new_s.append(None if s is None else
                     jnp.concatenate([sb.astype(s.dtype), s[f:]], axis=0))
    payload = jax.tree.unflatten(treedef, new_p)
    sidecar = None if enc.sidecar is None else \
        jax.tree.unflatten(treedef, new_s)
    return _dc.replace(enc, payload=payload, sidecar=sidecar)


# -------------------------------------------------------------- state
@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=("opt", "tstates", "astate", "cres", "bstate", "mstate"),
    meta_fields=())
@dataclasses.dataclass(frozen=True)
class TrainerState:
    """The one trainer-state container — a named, registered jit pytree.

    * ``opt``     — the optimizer's :class:`OptState` (always present);
    * ``tstates`` — per-transform state tuple (``()`` without stateful
      transforms; ``None`` entries for stateless ones);
    * ``astate``  — adaptive-attack plan-feedback state (``None`` unless
      the attack spec is adaptive);
    * ``cres``    — error-feedback compression residual (``None`` unless
      the codec spec has ``ef=1``);
    * ``bstate``  — the async bounded-staleness buffer
      (``repro.serve.buffer.BufferState``; ``None`` on the synchronous
      trainers — seed it with ``repro.serve.service.with_buffer``);
    * ``mstate``  — the device-resident observability carry
      (``{"m": repro.obs.MetricsState, "t": TraceState | None}``;
      ``None`` unless the step was built with an enabled
      ``repro.obs.ObsConfig`` — steps auto-seed it at trace time, scans
      seed it up front with ``repro.obs.init_train_obs``).

    Unused slots are ``None``/``()`` and flatten to zero leaves, so the
    container costs nothing under jit and checkpoints by field *name*
    (``state|opt|…``) — no consumer pattern-matches slot positions.  This
    replaced the PR-3/PR-4-era positional layouts (bare ``OptState`` /
    2- / 3- / 4-tuples); ``checkpoint.store.restore`` still reads those
    via the legacy key aliases (tests/test_trainer_state.py).
    """

    opt: OptState
    tstates: Tuple = ()
    astate: Any = None
    cres: Any = None
    bstate: Any = None
    mstate: Any = None


def as_trainer_state(state) -> TrainerState:
    """Coerce a bare :class:`OptState` (the pre-PR-5 plain layout) into a
    :class:`TrainerState`; pass a TrainerState through unchanged."""
    if isinstance(state, TrainerState):
        return state
    if isinstance(state, OptState):
        return TrainerState(opt=state)
    raise TypeError(
        f"expected TrainerState (or a bare OptState), got {type(state)}; "
        "seed trainer state with dist.init_train_state")


def _resolve_codec(codec):
    """Codec spec string / instance / None -> codec instance or None."""
    if codec is None or not isinstance(codec, str):
        return codec
    from repro.comm import codecs as CC
    return CC.get_codec(codec)


def _derive_mesh_ctx(shard_map_mesh, shard_map_axes, spmd
                     ) -> Optional[api.MeshContext]:
    """Resolve the (mesh, axes, spmd) trio both trainers accept.

    ``spmd=None`` auto-enables the mesh-native path whenever a mesh is
    given; ``shard_map_axes`` overrides the worker-axis derivation from
    the mesh's axis names (the satellite fix: the parameter is honored,
    not recorded-and-dropped).
    """
    if spmd is None:
        spmd = shard_map_mesh is not None
    if not spmd:
        return None
    if shard_map_mesh is None:
        raise ValueError("spmd aggregation needs shard_map_mesh")
    return api.MeshContext.for_mesh(
        shard_map_mesh,
        worker_axes=tuple(shard_map_axes) if shard_map_axes else None)


def replicate_on_mesh(tree: PyTree, mesh) -> PyTree:
    """``tree`` placed replicated over every device of ``mesh``: where
    ``launch/train.py --mesh`` keeps params and state."""
    from jax.sharding import NamedSharding, PartitionSpec
    return jax.device_put(tree, NamedSharding(mesh, PartitionSpec()))


def jit_train_step(step: Callable, mesh=None) -> Callable:
    """``jax.jit`` of a train step.  With ``mesh`` (a ``shard_map_mesh=``
    step whose params and state live replicated over it) every output is
    pinned to that placement.  Left to the compiler, the step hands some
    params and state back sharded like the apply's d-shards, and the next
    call, seeing a new input placement, compiles again."""
    if mesh is None:
        return jax.jit(step)
    from jax.sharding import NamedSharding, PartitionSpec
    return jax.jit(step, out_shardings=NamedSharding(mesh, PartitionSpec()))


def init_train_state(opt: Optimizer, params: PyTree,
                     transforms: Sequence[api.Transform] = (),
                     n_workers: int = 0, attack: str = "none",
                     attack_f: int = 0, codec=None) -> TrainerState:
    """Initial :class:`TrainerState` for :func:`make_train_step`.

    Plain runs get only the ``opt`` slot populated; stateful transforms
    (worker momentum) fill ``tstates`` with a per-worker state tuple
    mirroring the *stacked* gradient shapes (hence ``n_workers``); an
    adaptive attack spec (``adaptive_lie``, ``adaptive_mimic`` —
    ``core.attacks.ADAPTIVE``) fills ``astate``, seeded for ``attack_f``
    byzantine rows; an error-feedback codec spec
    (``"topk:frac=0.01,ef=1"`` — ``repro.comm.get_codec``) fills ``cres``
    with the per-worker compression residual.
    """
    opt_state = opt.init(params)
    stateful = any(t.stateful for t in transforms)
    adaptive = isinstance(attack, str) and ATK.is_adaptive(attack)
    codec_obj = _resolve_codec(codec)
    ef = codec_obj is not None and codec_obj.stateful
    if not stateful and not adaptive and not ef:
        return TrainerState(opt=opt_state)
    if n_workers <= 0:
        raise ValueError("stateful transforms / adaptive attacks / "
                         "error-feedback codecs need n_workers > 0")
    stacked = jax.tree.map(
        lambda p: jax.ShapeDtypeStruct((n_workers,) + p.shape, p.dtype),
        params)
    tstates: Tuple = ()
    if stateful:
        tstates = api.init_transform_states(transforms, stacked)
    astate = None
    if adaptive:
        astate = ATK.get_adaptive(attack).init_state(n_workers, attack_f)
    cres = codec_obj.init_residual(stacked) if ef else None
    return TrainerState(opt=opt_state, tstates=tstates, astate=astate,
                        cres=cres)


# ------------------------------------------------------------------ trainer
# The honest-mean deviation telemetry is shared between the stacked and
# streaming trainers (accumulate per block, finalise once) so the metric is
# numerically identical across substrates — campaign traces must be
# trainer-comparable.
def honest_dev_accumulate(dev_sq: jax.Array, ref_sq: jax.Array,
                          agg: PyTree, grads: PyTree, f_eff: int):
    """Add one (sub)tree's ||agg - honest_mean||² / ||honest_mean||² terms.

    ``grads`` is the stack the aggregator consumed (post-injection,
    post-transform); rows ``f_eff:`` of every leaf are the honest workers'
    values, so this measures the distance to the oracle that knew who was
    honest.
    """
    for a, g in zip(jax.tree.leaves(agg), jax.tree.leaves(grads)):
        hm = jnp.mean(g[f_eff:].astype(jnp.float32), axis=0)
        dev_sq = dev_sq + jnp.sum((a.astype(jnp.float32) - hm) ** 2)
        ref_sq = ref_sq + jnp.sum(hm ** 2)
    return dev_sq, ref_sq


def honest_dev_finalize(dev_sq: jax.Array, ref_sq: jax.Array) -> jax.Array:
    return jnp.sqrt(dev_sq) / (jnp.sqrt(ref_sq) + 1e-12)


def _honest_mean_dev(agg: PyTree, grads: PyTree, f_eff: int) -> jax.Array:
    """Relative l2 deviation of the aggregate from the honest-row mean."""
    zero = jnp.zeros((), jnp.float32)
    return honest_dev_finalize(
        *honest_dev_accumulate(zero, zero, agg, grads, f_eff))


def make_train_step(cfg: ArchConfig, rcfg: RobustConfig, opt: Optimizer,
                    lr_fn, *, window: int = 0, chunk_q: int = 1024,
                    attack: str = "none", attack_f: Optional[int] = None,
                    transforms: Sequence[api.Transform] = (),
                    codec: Optional[str] = None,
                    coord_chunk: int = 0, telemetry: bool = False,
                    grad_specs: Optional[PyTree] = None,
                    boundary_spec=None,
                    shard_map_mesh=None, shard_map_axes=None,
                    spmd: Optional[bool] = None,
                    hier=None,
                    obs: Optional[OBS.ObsConfig] = None):
    """Build the stacked-trainer step function (jit it yourself).

    ``attack`` is a spec string (``"little_is_enough:z=2.0"`` — see
    ``core.attacks.get_attack``); adaptive specs (``adaptive_lie``, …) make
    the state slot carry the attack's feedback state (seed it with
    :func:`init_train_state`).  ``attack_f`` is the number of rows the
    attack actually controls this phase (defaults to ``rcfg.f``, may be
    lower — the rule keeps defending against the full contract ``f``).

    ``codec`` puts a compressed wire between workers and aggregator
    (``repro.comm.get_codec`` specs — ``"qsgd:bits=8"``, ``"bf16"``, …):
    every worker *encodes* its gradient rows, byzantine injection then
    happens on the wire format — gradient-space attacks propose rows that
    get encoded like honest ones, wire attacks (``scale_poison``,
    ``payload_flip``) mutate payloads/sidecars directly — and the
    aggregator consumes the wire container (statistics straight off the
    quantized payloads under ``rcfg.use_pallas`` via the fused
    dequantize→stats kernel, apply on the decoded rows).  Error-feedback
    codecs (``ef=1``) thread a per-worker residual through the state
    (:func:`init_train_state`).

    ``obs`` — an enabled ``repro.obs.ObsConfig`` — makes the step record
    into the device-resident registry riding in ``TrainerState.mstate``
    (rounds counter, loss / grad-norm gauges + histogram, suspicion EMA
    under ``telemetry``) and ring-buffer stats→plan→apply span records
    (DESIGN.md §14).  Disabled or ``None`` compiles to the bitwise
    jaxpr of the uninstrumented step (tests/test_obs.py).

    With ``telemetry`` the metrics dict gains a ``"telemetry"`` sub-dict of
    plan diagnostics (``AggPlan.diagnostics``: per-worker selection mass,
    byzantine captured mass, Krum score spectrum, selection-boundary gap)
    plus ``honest_dev`` — campaign traces in ``repro.sim`` scan over these
    — and, under a codec, ``wire_bytes_per_worker``.

    ``grad_specs``/``shard_map_mesh``: optional PartitionSpec pytree pinned
    onto the stacked gradients (the transposed grad-stack layout the
    production mesh wants); ``boundary_spec`` threads to the model's remat
    boundaries.

    ``shard_map_mesh`` + ``spmd`` (default: on whenever a mesh is given)
    run the whole stats→plan→apply pipeline mesh-native (DESIGN.md §10):
    statistics shard the worker axis inside a shard_map (each device
    computes its row block of the (n, n) matrix), the apply phase shards
    d over the model axis.  ``shard_map_axes`` names the worker axes of
    that path explicitly (default: derived from the mesh's axis names —
    ``("pod", "data")`` multi-pod, ``("data",)`` otherwise).

    ``hier`` — a ``repro.hier.GroupConfig`` — replaces the flat
    stats→plan→apply with the two-level grouped pipeline (DESIGN.md §11):
    robust-aggregate within groups of ``hier.g`` workers, then over the
    group aggregates, with per-level f budgets derived and checked by
    ``core.theory.split_f_budget``.  Under a codec the group aggregates
    are re-encoded for the leaders→server hop (telemetry surfaces its
    byte count as ``leader_wire_bytes``); telemetry gains
    ``group_selection``, the outer level's per-group mass.  Not yet
    composable with the mesh-native (``spmd``) path or error-feedback
    codecs.
    """
    rcfg.validate()
    transforms = tuple(transforms)
    f_eff = rcfg.f if attack_f is None else attack_f
    if not 0 <= f_eff <= rcfg.f:
        raise ValueError(
            f"attack_f must be in [0, f] (attack_f={f_eff}, f={rcfg.f})")
    codec_obj = _resolve_codec(codec)
    wire = isinstance(attack, str) and ATK.is_wire_attack(attack)
    if wire and codec_obj is None:
        raise ValueError(
            f"wire attack {attack!r} needs a codec= wire to attack "
            f"(available codecs: see repro.comm.available_codecs())")
    adaptive = ATK.get_adaptive(attack) \
        if not wire and ATK.is_adaptive(attack) else None
    mesh_ctx = _derive_mesh_ctx(shard_map_mesh, shard_map_axes, spmd)
    # telemetry wants the score spectrum even for distance-free rules
    # (average / median campaigns report why they would have been rejected);
    # the backend is the same plan/apply pipeline robust serving and the
    # async service consume (DESIGN.md §13)
    backend = api.AggregatorBackend.for_config(
        rcfg, coord_chunk=coord_chunk, needs_dists=telemetry,
        mesh_ctx=mesh_ctx, obs=obs)
    needs_dists = backend.aggregator.needs_dists or telemetry
    obs_live = OBS.obs_on(obs)
    obs_trace = obs_live and obs.trace
    if hier is not None:
        if mesh_ctx is not None:
            raise NotImplementedError(
                "hier= is not composable with the mesh-native (spmd) "
                "aggregation path yet; drop shard_map_mesh/spmd")
        if codec_obj is not None and codec_obj.stateful:
            raise ValueError(
                "hier= does not support error-feedback codecs (the "
                "leaders→server hop has no residual slot); drop ef=1")

    def worker_loss(p, wb):
        return MD.loss_fn(p, cfg, wb, window=window, chunk_q=chunk_q,
                          boundary_spec=boundary_spec)

    def step(params, state, batch, key):
        state = as_trainer_state(state)
        opt_state, tstates = state.opt, state.tstates
        astate, cres = state.astate, state.cres
        mstate = state.mstate
        with OBS.scope("workers"):
            losses, grads = jax.vmap(
                lambda wb: jax.value_and_grad(worker_loss)(params, wb))(batch)
        if obs_live and mstate is None:
            # trace-time seed: the worker count is static here, and a jit
            # caller retraces once when None becomes a live carry.  Scans
            # seed up front instead (repro.obs.init_train_obs).
            mstate = OBS.init_train_obs(obs, losses.shape[0],
                                        telemetry=telemetry)
        obs_round = opt_state.step
        if adaptive is not None:
            atk = functools.partial(adaptive.propose, state=astate)
        else:
            atk = attack
        with OBS.scope("attack"):
            if not wire:
                # gradient-space adversary: proposes rows before encoding (it
                # controls its wire messages, so it encodes like anyone else)
                grads = inject_byzantine(grads, f_eff, atk, key)
            enc = None
            if codec_obj is not None:
                # distinct fold for quantization randomness: attack leaves use
                # fold_in(key, leaf_index), transforms 2^31-1 (below)
                ekey = jax.random.fold_in(key, 2 ** 31 - 2)
                enc, cres = codec_obj.encode(grads, key=ekey, residual=cres)
                if wire:
                    enc = inject_wire(enc, f_eff, attack, key)
                # the aggregator-side view: everything downstream (transforms,
                # apply, honest_dev) sees what survived the wire
                grads = codec_obj.decode(enc)
        if grad_specs is not None and shard_map_mesh is not None:
            from jax.sharding import NamedSharding
            grads = jax.lax.with_sharding_constraint(
                grads, jax.tree.map(
                    lambda s: NamedSharding(shard_map_mesh, s), grad_specs,
                    is_leaf=lambda x: not isinstance(x, dict)))
        # distinct fold for transform randomness: inject_byzantine consumes
        # fold_in(key, leaf_index), so a keyed transform must not draw from
        # the same stream as any attack leaf
        tkey = jax.random.fold_in(key, 2 ** 31 - 1)
        grads, tstates = api.apply_transforms(
            grads, transforms, tstates or None, key=tkey,
            use_pallas=rcfg.use_pallas)
        # statistics straight off the wire container (fused dequant→stats
        # under use_pallas) unless a transform rewrote the decoded stack
        stats_src = enc if (enc is not None and not transforms) else grads
        if hier is not None:
            from repro.hier import hier_aggregate_tree
            agg, plan, hinfo = hier_aggregate_tree(
                stats_src, rcfg.f, hier, codec=codec_obj, key=key,
                coord_chunk=coord_chunk, use_pallas=rcfg.use_pallas,
                needs_dists=needs_dists, obs=obs, obs_state=mstate,
                obs_round=obs_round)
            mstate = hinfo["obs_state"]
            stats = None
        else:
            # backend.plan validates stats.n against the actual batch
            # split (which RobustConfig's construction-time check never
            # saw) before any selection runs
            stats = backend.stats(stats_src)
            if obs_trace:
                mstate = {**mstate, "t": OBS.record(
                    mstate["t"], OBS.PH_STATS, obs_round)}
            plan = backend.plan(stats)
            if obs_trace:
                mstate = {**mstate, "t": OBS.record(
                    mstate["t"], OBS.PH_PLAN, obs_round,
                    jnp.max(plan.selection_weights()))}
            agg = backend.apply(plan, grads)
        if adaptive is not None:
            with OBS.scope("attack"):
                astate = adaptive.update(astate, plan.selection_weights())
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
                diag = plan.diagnostics(hinfo["inner_stats"]) \
                    if hier is not None else plan.diagnostics(stats)
                # count captured mass over the rows the attack actually holds
                # this phase (f_eff), not the rule's contract f
                diag["byz_mass"] = jnp.sum(diag["selection"][:f_eff])
                diag["honest_dev"] = _honest_mean_dev(agg, grads, f_eff)
                if enc is not None:
                    diag["wire_bytes_per_worker"] = jnp.asarray(
                        enc.bytes_per_worker, jnp.float32)
                if hier is not None and codec_obj is not None:
                    diag["leader_wire_bytes"] = jnp.asarray(
                        hinfo["leader_wire_bytes"], jnp.float32)
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
                TrainerState(opt=new_opt, tstates=tstates, astate=astate,
                             cres=cres, bstate=state.bstate,
                             mstate=mstate),
                metrics)

    return step
