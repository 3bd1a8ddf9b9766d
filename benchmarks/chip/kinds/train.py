"""Closed loop of robust training steps.

Set-up makes the weights on the device from the seed, compiles the
program's step (``dist.jit_train_step(dist.make_train_step(...))``, as
``launch/train.py`` builds it) and drives that one step object through the
traffic's ``first_steps`` steps; the window then runs further steps of the
same object, each on a fresh batch drawn from the seed and waited for.

The check runs the family's plain float32 reference (HIGHEST) over the
same first steps and compares, as the program's state left them: each
worker's loss at the first step; the first gradient as the optimizer got it (its momentum
after one step), leaf by leaf; and each leaf's change over the first
steps.  Norms are compared, not vectors: the gap between the program's
norm and the reference's, over the reference's norm of that leaf or of
the median leaf, whichever is larger.
"""
from __future__ import annotations

import functools
import math

import numpy as np

import reference_gar as R

#: leaves whose reference gradient is under this share of the median
#: leaf's are left out of the change: they move by rounding alone
NOUGHT = 1e-3
#: the answer_altered fault scales the step's update by this
ALTER = 2.0
#: the ways the timed path can be broken that a one-chip step can show
FAULTS = ("state_unchanged", "half_batch", "answer_altered")


def fp8_dot(jax):
    """Matrix products on float8 (e4m3) operands, scaled per tensor, in
    both passes: the control, one precision below the configuration's
    bfloat16."""
    jnp = jax.numpy
    hi = jax.lax.Precision.HIGHEST

    def q(x):
        s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
        return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s

    @functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
    def dot(spec, a, b):
        return jnp.einsum(spec, q(a), q(b), precision=hi)

    def fwd(spec, a, b):
        qa, qb = q(a), q(b)
        return jnp.einsum(spec, qa, qb, precision=hi), (qa, qb)

    def bwd(spec, res, ct):
        _, vjp = jax.vjp(
            lambda x, y: jnp.einsum(spec, x, y, precision=hi), *res)
        return vjp(q(ct))
    dot.defvjp(fwd, bwd)
    return dot


class Runner:
    unit = "step"

    def __init__(self, cell, jax, devs, *, variant="program", log=print):
        self.cell, self.jax, self.log = cell, jax, log
        self.variant = variant
        t, cfg = cell.traffic, cell.config
        self.n, self.f, self.gar = t["n_workers"], t["f"], t["gar"]
        self.b = t["per_worker_batch"]
        self.s = cfg["max_target_positions"] if cell.rehearse \
            else t["decoder_len"]
        self.chunk_q = min(t["chunk_q"], self.s)
        self.first = t["first_steps"]
        self.failed = 0

    # ------------------------------------------------------------ program
    def build(self):
        jax, jnp = self.jax, self.jax.numpy
        from repro.configs import RobustConfig
        from repro.dist import init_train_state, jit_train_step, \
            make_train_step
        from repro.optim import make_optimizer
        import harness
        t, cfg, fam = self.cell.traffic, self.cell.config, self.cell.family
        arch = fam.program_config(cfg)
        self.opt = make_optimizer(t["optimizer"], momentum=t["momentum"])
        lr = t["lr"]
        step = make_train_step(
            arch, RobustConfig(n_workers=self.n, f=self.f, gar=self.gar,
                               use_pallas=t["use_pallas"]),
            self.opt, lambda _: lr, chunk_q=self.chunk_q, attack=t["attack"])
        n, b, s = self.n, self.b, self.s
        self.init = jax.jit(functools.partial(fam.init_params, cfg=cfg))
        self.batch = jax.jit(lambda key, i: fam.make_batch(
            jax.random.fold_in(key, i), cfg, n, b, s))
        self.state_of = jax.jit(lambda p: init_train_state(
            self.opt, p, n_workers=n, attack=t["attack"], attack_f=self.f))
        key = jax.eval_shape(lambda: harness.seed_key(jax, 0))
        p = jax.eval_shape(self.init, key)
        args = (p, jax.eval_shape(self.state_of, p),
                jax.eval_shape(self.batch, key, 0), key)
        self.compiled = jit_train_step(step).lower(*args).compile()
        self.log(f"[train] memory_analysis "
                 f"{self.compiled.memory_analysis()}")
        self.half = jax.jit(fam.half_batch)
        self.alter = jax.jit(lambda old, new: jax.tree.map(
            lambda o, x: o + ALTER * (x - o), old, new))

        def norms(tree):
            return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(
                x.astype(jnp.float32)))) for x in jax.tree.leaves(tree)])
        self.norms = jax.jit(norms)
        self.change = jax.jit(lambda a, b: norms(
            jax.tree.map(lambda x, y: x - y, a, b)))

    def prepare(self, seed: int):
        import harness
        jax = self.jax
        key = harness.seed_key(jax, seed)
        self.keys = [jax.random.fold_in(key, i) for i in range(3)]
        self.params = self.init(self.keys[0])
        self.state = self.state_of(self.params)
        start = self.params
        self.losses = []
        for i in range(self.first):
            self.losses.append(self.advance(i))
            if i == 0:
                self.mu_norms = np.asarray(self.norms(self.state.opt.mu))
        self.change_norms = np.asarray(self.change(self.params, start))
        self.log(f"[train] first steps' losses {self.losses}")

    def advance(self, i: int) -> float:
        """One step of the program; the host spans name what the host
        does while the chip may sit idle."""
        jax = self.jax
        span = jax.profiler.TraceAnnotation
        with span("bench:batch"):
            batch = self.batch(self.keys[1], i)
            if self.variant == "half_batch":
                batch = self.half(batch)
        with span("bench:dispatch"):
            params, state, m = self.compiled(
                self.params, self.state, batch, jax.random.fold_in(
                    self.keys[2], i))
            if self.variant == "answer_altered":
                params = self.alter(self.params, params)
            elif self.variant == "state_unchanged":
                params, state = self.params, self.state
        self.params, self.state = params, state
        with span("bench:wait"):
            loss = float(m["loss"])
            jax.block_until_ready((params, state))
        if i == 0:
            self.first_losses = np.asarray(m["loss_per_worker"])
        return loss

    def one(self, i: int):
        if not math.isfinite(self.advance(self.first + i)):
            self.failed += 1

    # ------------------------------------------------------------ metrics
    def end_to_end(self, seconds: float, units: int) -> dict:
        return {"step_s": seconds / units}

    def flops_per_unit(self) -> float:
        return self.cell.family.step_flops(self.cell.config, self.n, self.b,
                                           self.s)

    def least_bytes_per_unit(self):
        return None

    def hlo_text(self) -> str:
        return self.compiled.as_text()

    # -------------------------------------------------------------- check
    def finish(self, keep_program: bool = False):
        del self.params, self.state
        if not keep_program:
            del self.compiled

    def reference(self, dot) -> dict:
        """The first steps of the plain reference with ``dot`` as every
        matrix product: losses, first-gradient norms, change norms."""
        jax, jnp = self.jax, self.jax.numpy
        cfg, t, fam = self.cell.config, self.cell.traffic, self.cell.family
        f, lr, mom = self.f, t["lr"], t["momentum"]
        vg = jax.jit(jax.value_and_grad(
            lambda p, bw: fam.reference_loss(p, cfg, bw, dot)))
        byz = jax.jit(lambda g: jax.tree.map(lambda x: jnp.concatenate(
            [jnp.broadcast_to(-jnp.mean(x[f:], axis=0), (f,) + x.shape[1:]),
             x[f:]]), g))
        sgd = jax.jit(lambda p, m, g: (
            jax.tree.map(lambda p, m, g: p - lr * (mom * m + g), p, m, g),
            jax.tree.map(lambda m, g: mom * m + g, m, g)))
        stack = jax.jit(lambda gs: jax.tree.map(
            lambda *xs: jnp.stack(xs), *gs))
        params = self.init(self.keys[0])
        start = params
        mu = jax.tree.map(jnp.zeros_like, params)
        out = {"losses": []}
        for i in range(self.first):
            batch = self.batch(self.keys[1], i)
            losses, grads = [], []
            for w in range(self.n):
                loss, g = vg(params, jax.tree.map(lambda x: x[w], batch))
                losses.append(float(loss))
                grads.append(g)
            g = byz(stack(grads))
            del grads
            agg = R.aggregate_tree(g, f, "f32")
            del g
            params, mu = sgd(params, mu, agg)
            out["losses"].append(float(np.mean(losses)))
            if i == 0:
                out["first_losses"] = np.asarray(losses)
                out["mu_norms"] = np.asarray(self.norms(mu))
        out["change_norms"] = np.asarray(self.change(params, start))
        return out

    def numbers(self, got: dict, ref: dict) -> dict:
        lp, lr = got["first_losses"], ref["first_losses"]
        gm, rm = got["mu_norms"], ref["mu_norms"]
        med = float(np.median(rm))
        keep = rm >= NOUGHT * med
        gc, rc = got["change_norms"][keep], ref["change_norms"][keep]
        medc = float(np.median(rc))
        return {
            # each worker's loss at the first step: later losses follow
            # plans that a bfloat16 program and a float32 reference may
            # pick apart, and the mean over workers lets rounding cancel
            "first_loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr))),
            "grad_gap": float(np.max(np.abs(gm - rm)
                                     / np.maximum(rm, med))),
            "change_gap": float(np.max(np.abs(gc - rc)
                                       / np.maximum(rc, medc))),
        }

    def got(self) -> dict:
        return {"losses": self.losses, "first_losses": self.first_losses,
                "mu_norms": self.mu_norms,
                "change_norms": self.change_norms}

    def check(self, seed: int) -> dict:
        jax = self.jax
        self.ref = self.reference(functools.partial(
            jax.numpy.einsum, precision=jax.lax.Precision.HIGHEST))
        rm = self.ref["mu_norms"]
        left = np.flatnonzero(rm < NOUGHT * np.median(rm)).tolist()
        self.log(f"[train] reference losses {self.ref['losses']}; leaves "
                 f"left out of the change (gradient under {NOUGHT} of the "
                 f"median leaf's): {left}")
        return self.numbers(self.got(), self.ref)

    def control(self) -> dict:
        """The reference on float8 operands against itself at float32
        (call after :meth:`check`)."""
        return self.numbers(self.reference(fp8_dot(self.jax)), self.ref)

    def fault_numbers(self) -> dict:
        return self.numbers(self.got(), self.ref)

    def release(self):
        pass
