#!/usr/bin/env python3
"""Smoke test: the Byzantine-robust training step on a TPU.

One process; the phases run in order and every check is printed.  The
script exits non-zero, without a result line, when JAX finds no TPU, when
a phase raises, or when any check failed (after the remaining phases ran,
so one run reports every check).

One chip (no arguments), whisper-tiny at its published size (4+4 layers,
d_model 384, vocab 51865, 1500 frames), n=11 workers, f=2, multi_bulyan,
sign_flip attack, per-worker batch 2, decoder length 448:

  (a) platform, device kind, device count, JAX version, compile cache;
  (b) ``STEPS`` steps of the stacked trainer (``make_train_step``, as
      ``launch/train.py`` builds it) with the Pallas kernels
      (``use_pallas=True``): per-step loss, step time after warm-up (ended
      by ``block_until_ready``), compiles inside the timed steps, peak
      device memory, and the leaves and elements each kernel took;
  (c) the same steps from the same seed with ``use_pallas=False``: equal
      plans every step, losses within ``LOSS_RTOL``;
  (d) one real whisper gradient tree through ``aggregate_tree`` with every
      leaf forced through ``pairwise_stats`` + ``fused_select``
      (``fused="force"``) against the XLA substrate;
  (e) ``tpu_custom_call`` in the compiled step of (b).

``--four-chips`` runs only the mesh-native path (``MeshContext`` on the
``make_host_mesh()`` 2x2 mesh) and what it is compared with: ``STEPS``
stacked steps with ``shard_map_mesh=`` (jitted and placed as
``launch/train.py --mesh`` does) against the replicated step, then, on
one gradient tree, sharded ``compute_stats`` bitwise with the kernels
(within ``MESH_STATS_RTOL`` on the XLA substrate), equal plans, and
sharded ``aggregate_tree`` within ``MESH_ATOL`` of the one-device
result.  Its programs all compile before any of them runs, the
one-device ones in threads, and none may compile again when called.

The last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.

  python chip_smoke.py
  python chip_smoke.py --four-chips
  JAX_PLATFORMS=cpu python chip_smoke.py --rehearse        # CPU rehearsal
  XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
      python chip_smoke.py --rehearse --four-chips

``--rehearse`` runs the same phases on the CPU at the reduced whisper
size with the kernels interpreted.  It checks control flow only: it
prints the aggregate comparisons without checking them (see
:func:`compare_apply`), skips the ``tpu_custom_call`` check and prints no
result line.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

N_WORKERS, F, GAR, ATTACK = 11, 2, "multi_bulyan", "sign_flip"
#: trainer steps per run; the first is the warm-up
STEPS = 4
SEED = 0
PER_WORKER_BATCH = 2
#: whisper's published text context (decoder positions)
DECODER_LEN = 448
#: |loss(pallas) - loss(xla)| <= LOSS_RTOL * |loss(xla)|.  The two runs
#: share the bf16 forward/backward; they differ only in the float
#: association of the stats and apply phases (HIGHEST-precision f32
#: einsums on both sides), i.e. by ~1e-7 relative in the aggregate and
#: lr times that in the parameters.  That moves the mean loss by far less
#: than one bf16 rounding step (2^-8 ~ 3.9e-3 relative), which bounds
#: what a different fusion of the bf16 forward may change; 1e-3 sits
#: between the two.
LOSS_RTOL = 1e-3
#: sharded vs one-device apply (the tests/test_spmd.py bound)
MESH_ATOL = 1e-6
#: sharded vs one-device statistics on the XLA substrate, entry by entry:
#: |a_ij - b_ij| <= MESH_STATS_RTOL * (|x_i|^2 + |x_j|^2), and
#: |a_i - b_i| <= MESH_STATS_RTOL * |x_i|^2 for the norms.  The kernels
#: fix their summation order, so the Pallas statistics are compared bit
#: for bit; XLA on a TPU tiles the per-device (n/W, n) gram and the
#: one-device (n, n) gram differently.  Each distance is
#: |x_i|^2 + |x_j|^2 - 2<x_i, x_j>, f32 sums of up to 2^25 terms per leaf
#: whose magnitudes add up to at most |x_i|^2 + |x_j|^2, so reassociating
#: them moves it by ~2 log2(d) 2^-24 ~ 3e-6 of that.  The plan, which is
#: what the statistics decide, is still compared exactly.
MESH_STATS_RTOL = 1e-5


class Checks:
    """Prints each check and keeps the failed ones."""

    def __init__(self):
        self.failed: list = []

    def __call__(self, ok: bool, what: str) -> bool:
        print(f"[check] {'PASS' if ok else 'FAIL'} {what}", flush=True)
        if not ok:
            self.failed.append(what)
        return ok


def parse_args(argv=None):
    ap = argparse.ArgumentParser(
        description="Smoke test of the robust training step on a TPU")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the mesh-native path on a 2x2 mesh")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal: reduced whisper, interpreted "
                         "kernels, no result line")
    return ap.parse_args(argv)


# ------------------------------------------------------------------ setup
def device_info(jax, rehearse: bool, need: int):
    """Phase (a).  Returns the device dict of the result line."""
    devs = jax.devices()
    d0 = devs[0]
    info = {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}
    print(f"[a] platform={info['platform']} kind={info['kind']} "
          f"count={info['count']} jax={jax.__version__}", flush=True)
    if d0.platform != "tpu" and not rehearse:
        print(f"chip_smoke: no TPU: JAX found platform {d0.platform!r}; "
              f"this script runs on a TPU only (--rehearse is the CPU "
              f"rehearsal)", file=sys.stderr)
        sys.exit(2)
    if len(devs) < need:
        print(f"chip_smoke: needs {need} devices, JAX found {len(devs)}",
              file=sys.stderr)
        sys.exit(2)
    return info


def cache_events():
    """Counts the persistent compile cache's hits and misses."""
    from jax import monitoring
    counts = {"hits": 0, "misses": 0}
    names = {"/jax/compilation_cache/cache_hits": "hits",
             "/jax/compilation_cache/cache_misses": "misses"}

    def listen(event, **kw):
        if event in names:
            counts[names[event]] += 1
    monitoring.register_event_listener(listen)
    return counts


class Setup:
    """The trainer's inputs, built as ``launch/train.py`` builds them."""

    def __init__(self, rehearse: bool):
        import jax
        from repro.configs import get_config
        from repro.data import lm_batches
        from repro.dist import init_train_state
        from repro.launch.train import worker_batch
        from repro import models as MD
        from repro.optim import make_optimizer, warmup_cosine

        cfg = get_config("whisper-tiny")
        seq = DECODER_LEN
        if rehearse:
            cfg, seq = cfg.reduced(), 16
        self.cfg, self.seq = cfg, seq
        self.chunk_q = min(seq, 512)
        self.key = jax.random.key(SEED)
        self.opt = make_optimizer("sgd", momentum=0.9)
        self.lr_fn = warmup_cosine(0.05, warmup=max(STEPS // 20, 1),
                                   total_steps=STEPS)
        self.params = MD.init_model(self.key, cfg)
        self.state = init_train_state(self.opt, self.params,
                                      n_workers=N_WORKERS, attack=ATTACK,
                                      attack_f=F)
        data = lm_batches(cfg.vocab_size, N_WORKERS * PER_WORKER_BATCH, seq,
                          seed=SEED)
        self.batches = [worker_batch(cfg, next(data), self.key, i, N_WORKERS)
                        for i in range(STEPS)]
        n_params = sum(x.size for x in jax.tree.leaves(self.params))
        print(f"[setup] arch={cfg.name} params={n_params:,} "
              f"workers={N_WORKERS} f={F} gar={GAR} attack={ATTACK} "
              f"per_worker_batch={PER_WORKER_BATCH} decoder_len={seq} "
              f"frames={cfg.n_frames} steps={STEPS}", flush=True)

    def step_fn(self, use_pallas: bool, mesh=None):
        from repro.configs import RobustConfig
        from repro.dist import jit_train_step, make_train_step
        rcfg = RobustConfig(n_workers=N_WORKERS, f=F, gar=GAR,
                            use_pallas=use_pallas)
        # telemetry: the plan's per-worker selection, compared across runs
        return jit_train_step(make_train_step(
            self.cfg, rcfg, self.opt, self.lr_fn, chunk_q=self.chunk_q,
            attack=ATTACK, telemetry=True, shard_map_mesh=mesh), mesh)

    def grads_fn(self):
        """Jitted: one step's stacked worker gradients, sign_flip rows
        injected: what the step hands the aggregator."""
        import jax
        from repro.dist.trainer import inject_byzantine
        from repro import models as MD

        def grads(params, wb, key):
            g = jax.vmap(jax.grad(
                lambda p, b: MD.loss_fn(p, self.cfg, b,
                                        chunk_q=self.chunk_q)),
                in_axes=(None, 0))(params, wb)
            return inject_byzantine(g, F, ATTACK, key)
        return jax.jit(grads)

    def grads_args(self):
        return self.params, self.batches[0], self.key

    def grad_tree(self):
        return self.grads_fn()(*self.grads_args())


def substrate_counts(records) -> str:
    acc: dict = {}
    for r in records:
        leaves, elems = acc.get(r.kernel, (0, 0))
        acc[r.kernel] = (leaves + 1, elems + r.n * r.d)
    return " ".join(f"{k}: {v[0]} leaves, {v[1]:,} elements"
                    for k, v in sorted(acc.items()))


class Run:
    """Per-step losses, plan selections and byzantine mass of one run,
    and its final parameters."""

    def __init__(self):
        self.losses, self.sels, self.byz, self.times = [], [], [], []
        self.params = None


def run_steps(jax, check, setup: Setup, fn, label: str, *,
              mesh=None, precompiled: bool = False) -> Run:
    """Steps ``fn`` (jitted or compiled) over the setup's batches; step 0
    is the warm-up, the rest are timed and must not compile, nor, when
    ``fn`` is ``precompiled`` (its lowering compiled), may step 0.  With
    ``mesh`` the parameters and state start replicated over it, as
    ``launch/train.py --mesh`` places them."""
    from repro.analysis.jaxpr_audit import CompileCounter
    from repro.dist import replicate_on_mesh
    run = Run()
    params, state = setup.params, setup.state
    if mesh is not None:
        params, state = replicate_on_mesh((params, state), mesh)

    def one(i, wb):
        nonlocal params, state
        t0 = time.perf_counter()
        params, state, m = fn(params, state, wb,
                              jax.random.fold_in(setup.key, i))
        jax.block_until_ready((params, state, m))
        run.times.append(time.perf_counter() - t0)
        run.losses.append(float(m["loss"]))
        run.sels.append(jax.device_get(m["telemetry"]["selection"]))
        run.byz.append(float(m["telemetry"]["byz_mass"]))
        print(f"[{label}] step {i} loss {run.losses[-1]:.6f} "
              f"byz_mass {run.byz[-1]:.4f} time {run.times[-1]:.6f} s",
              flush=True)

    with CompileCounter() as warm_up:
        one(0, setup.batches[0])
    if precompiled:
        ran_precompiled(check, label, warm_up, fn)
    with CompileCounter() as cc:
        for i, wb in enumerate(setup.batches[1:], start=1):
            one(i, wb)
    timed = run.times[1:]
    if timed:
        print(f"[{label}] step time after warm-up: mean "
              f"{sum(timed) / len(timed):.6f} s over {len(timed)} steps "
              f"(host clock, block_until_ready); compiles in the timed "
              f"steps: {cc.count}", flush=True)
        check(cc.count == 0, f"{label}: no compile inside the timed steps")
    run.params = params
    return run


def ran_precompiled(check, label: str, cc, *fns) -> None:
    """Checks that the calls counted by ``cc`` compiled none of ``fns``
    again.  Other compiles there are the transfers that place an input
    on a mesh (``jit(_multi_slice)``); they are printed."""
    own = {f"jit({fn.__name__})" for fn in fns}
    again = [n for n in cc.names if n in own]
    print(f"[{label}] compiles in the first call: {cc.names}", flush=True)
    check(not again, f"{label}: the first call runs the precompiled "
                     f"program ({len(again)} compiles of {sorted(own)})")


def peak_bytes(jax) -> str:
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported by this backend" if peak is None else f"{peak:,} B"


# ------------------------------------------------------------ comparisons
def compare_apply(check, label, got, ref, *, atol: float = 0.0,
                  rehearse: bool = False) -> None:
    """Leaf by leaf: every coordinate of ``got`` within ``atol`` of
    ``ref`` (0: equal).  A rehearsal prints the comparison unchecked: on
    the CPU the interpreted kernel's per-tile extraction einsums and XLA's
    whole-leaf ones round differently, so a kernel-vs-XLA comparison is
    neither equal nor within ``MESH_ATOL`` there."""
    import jax
    import numpy as np
    n_coords, worst, bad = 0, 0.0, []
    paths = jax.tree_util.tree_flatten_with_path(ref)[0]
    for (path, r), g in zip(paths, jax.tree.leaves(got)):
        r = np.asarray(r, np.float32)
        g = np.asarray(g, np.float32)
        diff = float(np.max(np.abs(g - r)))
        worst = max(worst, diff)
        n_coords += r.size
        if not diff <= atol:
            bad.append(jax.tree_util.keystr(path))
    print(f"[{label}] {n_coords:,} coordinates, max |diff| {worst:.6g}, "
          f"leaves past {atol}: {bad}", flush=True)
    if rehearse:
        print(f"[{label}] aggregate comparison: not checked (CPU "
              f"rehearsal)", flush=True)
        return
    check(not bad, f"{label}: every leaf's aggregate within {atol} of the "
                   f"reference")


def same_plan(check, label, a, b) -> None:
    import numpy as np
    ok = (np.array_equal(np.asarray(a.w_ext), np.asarray(b.w_ext))
          and np.array_equal(np.asarray(a.w_agr), np.asarray(b.w_agr))
          and a.beta == b.beta)
    check(ok, f"{label}: plan weights equal")


def plan_of(grads, **kw):
    import jax
    from repro.core import api
    agg = api.get_aggregator(GAR)
    stats = jax.jit(lambda g: api.compute_stats(
        g, F, needs_dists=True, **kw))(grads)
    return stats, agg.plan(stats)


# ----------------------------------------------------------------- phases
def one_chip(jax, check, rehearse: bool) -> None:
    import numpy as np
    from repro.core import api
    from repro.obs.profile import KernelProfiler

    setup = Setup(rehearse)

    # (b) the Pallas run; the step is compiled up front so that (e) reads
    # the very program the steps run
    step = setup.step_fn(use_pallas=True)
    t0 = time.perf_counter()
    with KernelProfiler() as prof:
        lowered = step.lower(setup.params, setup.state, setup.batches[0],
                             setup.key)
    compiled = lowered.compile()
    print(f"[b] compile {time.perf_counter() - t0:.3f} s; memory: "
          f"{compiled.memory_analysis()}", flush=True)
    print(f"[b] substrates: {substrate_counts(prof.records)}", flush=True)
    run_k = run_steps(jax, check, setup, compiled, "b")
    check(bool(np.all(np.isfinite(run_k.losses))), "b: finite losses")
    print(f"[b] peak_bytes_in_use {peak_bytes(jax)}", flush=True)

    # (e)
    if rehearse:
        print("[e] tpu_custom_call: not checked (kernels interpreted)")
    else:
        check("tpu_custom_call" in compiled.as_text(),
              "e: tpu_custom_call in the compiled step")
    del compiled, lowered, run_k.params

    # (c) the XLA run
    run_x = run_steps(jax, check, setup, setup.step_fn(False), "c")
    del run_x.params
    for i, (a, b) in enumerate(zip(run_k.sels, run_x.sels)):
        check(np.array_equal(a, b), f"c: step {i} plan selection equal")
    for i, (a, b) in enumerate(zip(run_k.losses, run_x.losses)):
        check(abs(a - b) <= LOSS_RTOL * abs(b),
              f"c: step {i} loss {a:.6f} vs {b:.6f} within {LOSS_RTOL} "
              f"relative")

    # (d) every leaf through the kernels vs the XLA substrate
    grads = setup.grad_tree()
    _, plan_k = plan_of(grads, use_pallas=True)
    _, plan_x = plan_of(grads)
    same_plan(check, "d", plan_k, plan_x)
    with KernelProfiler() as prof:
        agg_k = jax.jit(lambda g: api.aggregate_tree(
            g, F, GAR, use_pallas=True, fused="force"))(grads)
    agg_x = jax.jit(lambda g: api.aggregate_tree(g, F, GAR))(grads)
    print(f"[d] substrates: {substrate_counts(prof.records)}", flush=True)
    compare_apply(check, "d", agg_k, agg_x, rehearse=rehearse)


def stats_error(stats_1, stats_m) -> dict:
    """Largest sharded-vs-one-device difference of the distances and
    norms, each entry over its ``MESH_STATS_RTOL`` scale (see there)."""
    import numpy as np
    d1, dm = np.asarray(stats_1.dists), np.asarray(stats_m.dists)
    s1, sm = np.asarray(stats_1.sq_norms), np.asarray(stats_m.sq_norms)
    scale = s1[:, None] + s1[None, :]
    return {"dists": float(np.max(np.abs(dm - d1) / scale)),
            "sq_norms": float(np.max(np.abs(sm - s1) / s1))}


def stats_and_aggregate(**kw):
    """The jitted statistics, plan and ``aggregate_tree`` result of one
    gradient tree, in one program: one compile where ``plan_of`` and
    ``aggregate_tree`` would take two."""
    import jax
    from repro.core import api
    agg = api.get_aggregator(GAR)

    def run(g):
        stats = api.compute_stats(g, F, needs_dists=True, **kw)
        return stats, agg.plan(stats), api.aggregate_tree(g, F, GAR, **kw)
    return jax.jit(run)


def four_chips(jax, check, rehearse: bool) -> None:
    from concurrent.futures import ThreadPoolExecutor
    import numpy as np
    from repro.analysis.jaxpr_audit import CompileCounter
    from repro.core import api
    from repro.dist import replicate_on_mesh
    from repro.launch.mesh import make_host_mesh

    t0 = time.perf_counter()

    def phase(what):
        print(f"[time] {what} done at {time.perf_counter() - t0:.3f} s",
              flush=True)

    mesh = make_host_mesh()
    ctx = api.MeshContext.for_mesh(mesh)
    print(f"[mesh] shape={dict(mesh.shape)} worker axes={ctx.worker_axes} "
          f"model axis={ctx.model_axis}", flush=True)
    setup = Setup(rehearse)

    # Every program compiles before any runs: the one-device programs in
    # threads (XLA drops the GIL while it compiles), the three mesh
    # programs meanwhile in this thread, one at a time.  A four-chip run
    # that compiled the mesh programs in threads too crashed inside the
    # TPU compiler's SPMD partitioner (stack overflow).  A compiled
    # lowering fills the jit's own cache, so the calls below run what was
    # compiled here.
    sharded = setup.step_fn(True, mesh=mesh)
    replicated = setup.step_fn(True)
    grads_fn = setup.grads_fn()
    programs = {(use_pallas, sharded_stats): stats_and_aggregate(
                    use_pallas=use_pallas,
                    **({"mesh_ctx": ctx} if sharded_stats else {}))
                for use_pallas in (False, True)
                for sharded_stats in (False, True)}
    step_in = (setup.batches[0], jax.random.fold_in(setup.key, 0))
    with ThreadPoolExecutor(max_workers=4) as pool, \
            CompileCounter() as compiled:
        def in_thread(fn, *args):
            return pool.submit(fn.lower(*args).compile)

        def here(label, fn, *args):
            fn.lower(*args).compile()
            phase(f"compile of {label}")
        pending = [in_thread(replicated, setup.params, setup.state,
                             *step_in)]
        grads_compiled = in_thread(grads_fn, *setup.grads_args())
        here("the sharded step", sharded,
             *replicate_on_mesh((setup.params, setup.state), mesh),
             *step_in)
        grads_compiled.result()
        grads = grads_fn(*setup.grads_args())
        pending += [in_thread(programs[use_pallas, False], grads)
                    for use_pallas in (False, True)]
        for use_pallas in (False, True):
            here(f"the sharded statistics, pallas={use_pallas}",
                 programs[use_pallas, True], grads)
        for done in pending:
            done.result()
    print(f"[compile] {compiled.names}", flush=True)
    phase("compiles")

    # steps, jitted and placed as launch/train.py --mesh does, with the
    # bounds of test_sharded_train_step_matches_replicated: byzantine
    # capture equally bounded, params within the backward noise
    out = run_steps(jax, check, setup, sharded, "sharded", mesh=mesh,
                    precompiled=True)
    phase("sharded steps")
    ref = run_steps(jax, check, setup, replicated, "replicated",
                    precompiled=True)
    phase("replicated steps")
    for i, (a, b) in enumerate(zip(ref.byz, out.byz)):
        check(a <= 0.2 and b <= 0.2 and abs(a - b) <= 0.1,
              f"steps: step {i} byz_mass {a:.4f} vs {b:.4f} bounded")
    worst = max(float(np.max(np.abs(np.asarray(a, np.float32)
                                    - np.asarray(b, np.float32))))
                for a, b in zip(jax.tree.leaves(ref.params),
                                jax.tree.leaves(out.params)))
    check(worst <= 5e-2, f"steps: params within 5e-2 after "
                         f"{len(ref.losses)} steps (max |diff| "
                         f"{worst:.6g})")
    del ref, out

    # statistics, plan and aggregate of one gradient tree, sharded vs one
    # device, on the XLA substrate and then with the kernels
    for use_pallas in (False, True):
        label = f"mesh pallas={use_pallas}"
        one_device, sharded_stats = (programs[use_pallas, False],
                                     programs[use_pallas, True])
        with CompileCounter() as cc:
            stats_1, plan_1, agg_1 = one_device(grads)
            stats_m, plan_m, agg_m = sharded_stats(grads)
        ran_precompiled(check, label, cc, one_device, sharded_stats)
        err = stats_error(stats_1, stats_m)
        for name in ("dists", "sq_norms"):
            a = np.asarray(getattr(stats_1, name))
            b = np.asarray(getattr(stats_m, name))
            what = (f"{label}: sharded {name}, largest |diff| over its "
                    f"scale {err[name]:.6g}")
            if use_pallas:
                check(np.array_equal(a, b), f"{what}, bitwise")
            else:
                check(err[name] <= MESH_STATS_RTOL,
                      f"{what} <= {MESH_STATS_RTOL}")
        same_plan(check, label, plan_1, plan_m)
        compare_apply(check, label, agg_m, agg_1, atol=MESH_ATOL,
                      rehearse=rehearse)
        phase(label)


def main(argv=None) -> int:
    args = parse_args(argv)
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    cache = cache_events()
    info = device_info(jax, args.rehearse, need=4 if args.four_chips else 1)
    print(f"[a] compile cache {cache_dir}", flush=True)
    check = Checks()
    if args.four_chips:
        four_chips(jax, check, args.rehearse)
    else:
        one_chip(jax, check, args.rehearse)
    print(f"[cache] persistent compile cache: {cache['hits']} hits, "
          f"{cache['misses']} misses", flush=True)
    if check.failed:
        print(f"chip_smoke: {len(check.failed)} check(s) failed: "
              f"{check.failed}",
              file=sys.stderr)
        return 1
    if args.rehearse:
        print("chip_smoke: rehearsal passed (CPU, not a chip run)")
        return 0
    print(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
