"""End-to-end training driver.

Runs byzantine-robust training of a selectable architecture on the local
device(s).  On this CPU container it is used with reduced configs
(``--reduced``) and the ~100M example (examples/byzantine_training.py); on a
real TPU slice the same driver takes the production mesh path.

Steps are dispatched ahead of the device; only a logged loss waits.  With
``--obs`` or ``--profile-dir`` each step is instead three host spans,
``batch``, ``dispatch`` and ``wait`` (the step is waited for before the
next begins), inside a ``step`` span; they are written into a
``jax.profiler`` trace as ``repro:<name>``.  ``--profile-dir DIR`` traces
the last few steps into DIR: the device's operations there carry the
step's ``robust.*`` scopes and the kernels' names.

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b --reduced \\
      --steps 100 --workers 12 --f 2 --gar multi_bulyan --attack sign_flip
"""
from __future__ import annotations

import argparse
import statistics
import time

import jax
import jax.numpy as jnp

from repro.analysis.jaxpr_audit import CompileCounter
from repro.checkpoint import save
from repro.configs import ARCH_NAMES, RobustConfig, get_config
from repro.data import lm_batches
from repro.dist import (init_train_state, jit_train_step, make_train_step,
                        replicate_on_mesh, split_workers)
from repro.dist.streaming import make_streaming_train_step
from repro.launch.compile_cache import enable_compile_cache
from repro import models as MD
from repro import obs as OBS
from repro.optim import make_optimizer, warmup_cosine

#: how many of the last steps ``--profile-dir`` traces (never step 0,
#: which compiles)
PROFILE_STEPS = 3


def worker_batch(cfg, batch, key, step: int, n_workers: int):
    """One step's global token batch -> the (n_workers, per_worker, ...)
    stacks the trainer takes, with the stub encoder frames (enc-dec) or
    image prefix (VLM) the arch needs, drawn from ``key`` and ``step``."""
    b = batch["tokens"].shape[0]
    if cfg.is_encdec:
        batch["frames"] = jax.random.normal(
            jax.random.fold_in(key, 10_000 + step),
            (b, cfg.n_frames, cfg.d_model), dtype=jnp.bfloat16)
    if cfg.n_patches:
        batch["prefix_embeds"] = jax.random.normal(
            jax.random.fold_in(key, 20_000 + step),
            (b, cfg.n_patches, cfg.d_model), dtype=jnp.bfloat16)
    return split_workers(batch, n_workers)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=ARCH_NAMES, default="qwen2-1.5b")
    ap.add_argument("--reduced", action="store_true",
                    help="use the reduced smoke-scale variant (CPU)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--per-worker-batch", type=int, default=2)
    ap.add_argument("--workers", type=int, default=11)
    ap.add_argument("--f", type=int, default=2)
    ap.add_argument("--gar", default="multi_bulyan")
    ap.add_argument("--use-pallas", action="store_true",
                    help="route stats + bulyan apply through the Pallas "
                         "kernels (fused fast path; interpret mode on CPU)")
    ap.add_argument("--attack", default="none")
    ap.add_argument("--codec", default=None,
                    help="wire codec spec (repro.comm): qsgd:bits=8, bf16, "
                         "signsgd, topk:frac=0.01[,ef=1], fp32; attacks "
                         "then hit the wire format (scale_poison, "
                         "payload_flip are wire-level attacks)")
    ap.add_argument("--trainer", default="stacked",
                    choices=("stacked", "stream_block", "stream_global"))
    ap.add_argument("--hier", default=None, metavar="SPEC",
                    help="two-level grouped aggregation (repro.hier): "
                         "'g=64' groups workers into ceil(n/64) groups, "
                         "robust-aggregates within each, then across the "
                         "group outputs — O(n*g) instead of O(n^2) "
                         "selection. Optional keys: rule=, outer_rule=, "
                         "f_inner=, f_outer=, enforce=0 "
                         "(DESIGN.md §11)")
    ap.add_argument("--mesh", default="none",
                    choices=("none", "host", "production"),
                    help="run aggregation mesh-native (DESIGN.md §10): "
                         "'host' factors the local devices into a "
                         "(data, model) mesh (use XLA_FLAGS="
                         "--xla_force_host_platform_device_count=8 to "
                         "exercise real sharding on CPU), 'production' "
                         "builds the 256-chip pod mesh")
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke preset: --reduced, 3 steps, log every "
                         "step")
    ap.add_argument("--obs", action="store_true",
                    help="jit-safe runtime observability (DESIGN.md §14): "
                         "in-graph metrics registry + span ring in the "
                         "step, host wall-clock spans around it; drains "
                         "to an obs.v1 snapshot + a Perfetto/Chrome trace "
                         "after the run")
    ap.add_argument("--obs-json", default="obs_snapshot.json",
                    help="obs.v1 snapshot output path (with --obs)")
    ap.add_argument("--obs-trace", default="obs_trace.json",
                    help="Chrome-trace output path (with --obs); open at "
                         "https://ui.perfetto.dev")
    ap.add_argument("--profile-dir", default=None, metavar="DIR",
                    help=f"trace the last {PROFILE_STEPS} steps (never "
                         "step 0) with jax.profiler into DIR")
    ap.add_argument("--optimizer", default="sgd")
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)
    if args.smoke:
        args.reduced = True
        args.steps = min(args.steps, 3)
        args.log_every = 1
    if args.profile_dir and args.steps < 2:
        raise SystemExit("--profile-dir traces warm steps: it needs "
                         "--steps >= 2")
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if cfg.is_encdec and args.trainer != "stacked":
        raise SystemExit("enc-dec supports only the stacked trainer")

    mesh = None
    if args.mesh != "none":
        from repro.launch.mesh import make_host_mesh, make_production_mesh
        mesh = make_host_mesh() if args.mesh == "host" \
            else make_production_mesh()

    hier = None
    if args.hier:
        from repro.hier import GroupConfig
        hier = GroupConfig.from_spec(args.hier, rule=args.gar)
        budget = hier.budget(args.workers, args.f)
        print(f"[train] hier: {budget.n_groups} groups "
              f"{list(budget.group_sizes)} f_inner={budget.f_inner} "
              f"f_outer={budget.f_outer} inner={hier.rule} "
              f"outer={hier.resolve_outer_rule(budget)}")
    rcfg = RobustConfig(n_workers=args.workers, f=args.f, gar=args.gar,
                        use_pallas=args.use_pallas,
                        grouped=hier is not None)
    key = jax.random.key(args.seed)
    params = MD.init_model(key, cfg)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"[train] arch={cfg.name} params={n_params:,} workers={args.workers} "
          f"f={args.f} gar={args.gar} attack={args.attack} "
          f"codec={args.codec} trainer={args.trainer} "
          f"pallas={args.use_pallas}")
    if mesh is not None:
        print(f"[train] mesh={args.mesh} shape={dict(mesh.shape)} "
              f"(worker axis sharded over "
              f"{'pod×data' if 'pod' in mesh.axis_names else 'data'}, "
              f"d over model)")
    if args.codec:
        if hier is not None:
            from repro.comm import hier_wire_stats
            for ws in hier_wire_stats(args.codec, params, n=args.workers,
                                      g=hier.g):
                print(f"[train] wire[{ws.level}]: {ws.n} x "
                      f"{ws.bytes_per_worker:,} B/step "
                      f"({ws.compression:.1f}x vs fp32)")
        else:
            from repro.comm import wire_stats
            ws = wire_stats(args.codec, params, n=args.workers)
            print(f"[train] wire: {ws.bytes_per_worker:,} B/worker/step "
                  f"({ws.compression:.1f}x vs fp32, "
                  f"{ws.chunks_per_worker} chunk(s) of {ws.chunk_bytes:,} B)")

    opt = make_optimizer(args.optimizer,
                         **({"momentum": 0.9} if args.optimizer == "sgd" else {}))
    # seeds the adaptive-attack feedback slot when --attack is adaptive and
    # the error-feedback residual when --codec has ef=1 (plain OptState
    # otherwise)
    state = init_train_state(opt, params, n_workers=args.workers,
                             attack=args.attack, attack_f=args.f,
                             codec=args.codec)
    lr_fn = warmup_cosine(args.lr, warmup=max(args.steps // 20, 1),
                          total_steps=args.steps)
    chunk_q = min(args.seq, 512)
    # ring sized to retain the whole run (3-4 records/step); the jitted
    # steps lazily seed TrainerState.mstate at trace time, so no carry
    # surgery is needed here (unlike the sim engine's scan)
    obs = OBS.ObsConfig(enabled=True, ring=max(128, 4 * args.steps)) \
        if args.obs else None
    if args.trainer == "stacked":
        step_fn = make_train_step(cfg, rcfg, opt, lr_fn, chunk_q=chunk_q,
                                  attack=args.attack, codec=args.codec,
                                  shard_map_mesh=mesh, hier=hier, obs=obs)
    else:
        scope = "global" if args.trainer.endswith("global") else "block"
        step_fn = make_streaming_train_step(cfg, rcfg, opt, lr_fn,
                                            scope=scope, chunk_q=chunk_q,
                                            attack=args.attack,
                                            codec=args.codec,
                                            shard_map_mesh=mesh, hier=hier,
                                            obs=obs)
    step_fn = jit_train_step(step_fn, mesh)
    if mesh is not None:
        # the mesh step returns params and state replicated over the mesh;
        # start them there, so that every step sees one placement and the
        # step compiles once
        params, state = replicate_on_mesh((params, state), mesh)
    tracer = OBS.SpanTracer() if args.obs or args.profile_dir else None
    profiled = range(max(1, args.steps - PROFILE_STEPS), args.steps) \
        if args.profile_dir else range(0)

    global_batch = args.workers * args.per_worker_batch
    data = lm_batches(cfg.vocab_size, global_batch, args.seq, seed=args.seed)
    t0 = time.time()
    loss = float("nan")
    tracing = False
    with CompileCounter() as compiles:
        try:
            for i in range(args.steps):
                if i in profiled and not tracing:
                    jax.profiler.start_trace(args.profile_dir)
                    tracing = True
                if tracer is None:
                    wb = worker_batch(cfg, next(data), key, i, args.workers)
                    params, state, metrics = step_fn(
                        params, state, wb, jax.random.fold_in(key, i))
                else:
                    with tracer.span("step", round=i):
                        with tracer.span("batch"):
                            wb = worker_batch(cfg, next(data), key, i,
                                              args.workers)
                        with tracer.span("dispatch"):
                            params, state, metrics = step_fn(
                                params, state, wb, jax.random.fold_in(key, i))
                        with tracer.span("wait"):
                            jax.block_until_ready((params, state, metrics))
                if tracing and i == profiled[-1]:
                    t_stop = time.perf_counter()
                    jax.profiler.stop_trace()
                    write_s = time.perf_counter() - t_stop
                    tracing = False
                if i == 0:
                    first_step_compiles = compiles.count
                    jax.block_until_ready((params, state))
                    t_warm = time.perf_counter()
                if i % args.log_every == 0 or i == args.steps - 1:
                    loss = float(metrics["loss"])
                    print(f"[train] step {i:5d} loss {loss:.4f} "
                          f"lr {float(metrics['lr']):.2e} "
                          f"({(time.time()-t0)/(i+1):.2f}s/step)",
                          flush=True)
        finally:
            if tracing:
                jax.profiler.stop_trace()
    if args.steps > 1:
        jax.block_until_ready((params, state))
        warm_s = (time.perf_counter() - t_warm) / (args.steps - 1)
        print(f"[train] compiles after step 0: "
              f"{compiles.count - first_step_compiles}")
        print(f"[train] warm steps: {warm_s:.6f} s/step over "
              f"{args.steps - 1} steps (wall clock)")
    if tracer is not None and args.steps > 1:
        steps = {s["args"]["round"]: s["dur_us"] * 1e-6
                 for s in tracer.spans if s["name"] == "step"}
        warm = [t for i, t in steps.items() if i >= 1]
        print(f"[train] warm step time: median "
              f"{statistics.median(warm):.6f} s over {len(warm)} steps")
    if profiled:
        traced = statistics.median(steps[i] for i in profiled)
        print(f"[train] profile: steps {profiled[0]}..{profiled[-1]} "
              f"traced -> {args.profile_dir}; median step {traced:.6f} s; "
              f"written in {write_s:.3f} s")
    if args.ckpt_dir:
        path = save(args.ckpt_dir, args.steps, {"params": params})
        print(f"[train] checkpoint -> {path}")
    if args.obs and state.mstate is not None:
        recs = OBS.drain(state.mstate.get("t"))
        snap = OBS.snapshot(
            metrics=state.mstate["m"], trace_records=recs,
            meta={"source": "launch.train", "arch": cfg.name,
                  "trainer": args.trainer, "steps": args.steps,
                  "workers": args.workers, "f": args.f, "gar": args.gar,
                  "attack": args.attack})
        OBS.write_snapshot(args.obs_json, snap)
        n_ev = OBS.export_chrome_trace(
            args.obs_trace, device_records=recs, host_spans=tracer.spans,
            meta={"source": "launch.train", "arch": cfg.name})
        print(f"[train] obs: {len(recs)} span records, "
              f"counters {snap['metrics']['counters']} "
              f"-> {args.obs_json}, {n_ev} trace events -> "
              f"{args.obs_trace}")
    print(f"[train] done: final loss {loss:.4f}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
