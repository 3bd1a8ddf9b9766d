"""Closed loop of aggregation rounds.

One float32 gradient stack with the configuration's leaf shapes is made on
the device from the seed; every round is one call of the program's jitted
``core.api.aggregate_tree`` on it, waited for before the next, as a
parameter server waits for the aggregate before it broadcasts.  One stack
serves the whole window: a round's cost does not depend on the values.

The check samples coordinates of every leaf from the seed and compares the
last round's aggregate there with the plain multi-Bulyan of
``reference_gar`` (float32); a coordinate whose reference
selection is decided by less than ``TIE_MARGIN`` of its scale is left out.
"""
from __future__ import annotations

import math

import numpy as np

import reference_gar as R

#: coordinates whose two nearest candidates lie closer than this share of
#: the coordinate's scale are not compared: rounding may pick either
TIE_MARGIN = 1e-5
#: the answer_altered fault scales one leaf's aggregate by this
ALTER = 1.0 + 1e-3
#: the ways the timed path can be broken that this kind can show: one
#: chip, no state carried between rounds, no batch to average over
FAULTS = ("answer_altered",)


class Runner:
    unit = "round"

    def __init__(self, cell, jax, devs, *, variant="program", log=print):
        self.cell, self.jax, self.log = cell, jax, log
        self.variant = variant
        t = cell.traffic
        self.n, self.f, self.gar = t["n_workers"], t["f"], t["gar"]
        self.shapes = cell.family.param_shapes(cell.config)
        self.failed = 0

    # ------------------------------------------------------------- inputs
    def _leaf_shapes(self):
        return self.jax.tree.leaves(
            self.shapes, is_leaf=lambda x: isinstance(x, tuple))

    def make_stack(self, seed: int):
        """One jitted call: honest rows ``u + s_i z_i``, f rows the negated
        honest mean; ``s_i`` the traffic's noise scales in a seeded order."""
        import harness
        jax, jnp = self.jax, self.jax.numpy
        scales = np.random.default_rng(seed).permutation(
            np.asarray(self.cell.traffic["noise_scales"], np.float32))
        if len(scales) != self.n - self.f:
            raise ValueError("one noise scale per honest worker")
        key = harness.seed_key(jax, seed)
        treedef = jax.tree.structure(
            self.shapes, is_leaf=lambda x: isinstance(x, tuple))
        shapes, n, f = self._leaf_shapes(), self.n, self.f

        def make(key, scales):
            out = []
            for i, shape in enumerate(shapes):
                ku, kz = jax.random.split(jax.random.fold_in(key, i))
                u = jax.random.normal(ku, shape, jnp.float32)
                z = jax.random.normal(kz, (n - f,) + shape, jnp.float32)
                s = scales.reshape((n - f,) + (1,) * len(shape))
                honest = u[None] + s * z
                byz = -jnp.mean(honest, axis=0)
                out.append(jnp.concatenate(
                    [jnp.broadcast_to(byz, (f,) + shape), honest]))
            return jax.tree.unflatten(treedef, out)
        return jax.jit(make)(key, jnp.asarray(scales))

    # ------------------------------------------------------------ program
    def build(self):
        jax = self.jax
        from repro.core import api
        from repro.obs.profile import KernelProfiler
        use_pallas = self.cell.traffic["use_pallas"]
        f, gar = self.f, self.gar
        self.fn = jax.jit(lambda s: api.aggregate_tree(
            s, f, gar, use_pallas=use_pallas))
        spec = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((self.n,) + s, jax.numpy.float32),
            self.shapes, is_leaf=lambda x: isinstance(x, tuple))
        with KernelProfiler() as prof:
            lowered = self.fn.lower(spec)
        self.compiled = lowered.compile()
        acc = {}
        for r in prof.records:
            leaves, elems = acc.get(r.kernel, (0, 0))
            acc[r.kernel] = (leaves + 1, elems + r.n * r.d)
        self.log(f"[agg] substrates (leaves, stack elements): {acc}")
        self.log(f"[agg] memory_analysis {self.compiled.memory_analysis()}")
        self.alter = jax.jit(lambda out: jax.tree.unflatten(
            jax.tree.structure(out),
            [jax.tree.leaves(out)[0] * ALTER] + jax.tree.leaves(out)[1:]))

    def prepare(self, seed: int):
        self.seed = seed
        self.stack = None
        self.stack = self.make_stack(seed)
        self.out = self._round()     # warm-up: the first call
        self.jax.block_until_ready(self.out)

    def _round(self):
        out = self.compiled(self.stack)
        if self.variant == "answer_altered":
            out = self.alter(out)
        return out

    def one(self, i: int):
        """One round; the host spans name what the host does while the
        chip may sit idle."""
        span = self.jax.profiler.TraceAnnotation
        self.out = None      # one aggregate alive at a time: memory
        with span("bench:dispatch"):
            self.out = self._round()
        with span("bench:wait"):
            self.jax.block_until_ready(self.out)

    # ------------------------------------------------------------ metrics
    def end_to_end(self, seconds: float, units: int) -> dict:
        return {"round_ms": 1e3 * seconds / units}

    def coords(self) -> int:
        return sum(math.prod(s) for s in self._leaf_shapes())

    def flops_per_unit(self) -> float:
        """Statistics (gram, n^2 d multiply-adds) and the two extraction
        products (theta n d each) a round requires."""
        theta = self.n - 2 * self.f - 2
        return 2.0 * self.coords() * (self.n * self.n + 2 * theta * self.n)

    def least_bytes_per_unit(self) -> float:
        """The float32 stack read twice (statistics, apply) and the
        aggregate written once."""
        return 4.0 * self.coords() * (2 * self.n + 1)

    def hlo_text(self) -> str:
        return self.compiled.as_text()

    # -------------------------------------------------------------- check
    def finish(self, keep_program: bool = False):
        """Keeps the last aggregate at the sampled coordinates, then frees
        the program's output and, unless kept, its executable."""
        jax = self.jax
        rng = np.random.default_rng([self.seed, 1])
        k = self.cell.traffic["sample_per_leaf"]
        self.index = [R.sample_index(s, k, rng) for s in self._leaf_shapes()]
        self.got = [np.asarray(jax.device_get(leaf[idx]))
                    for leaf, idx in zip(jax.tree.leaves(self.out),
                                         self.index)]
        del self.out
        if not keep_program:
            del self.compiled

    def readings(self, precision):
        """The reference's aggregate at the sampled coordinates, its tie
        margins and the coordinates' scales, at ``precision``."""
        jax = self.jax
        leaves = jax.tree.leaves(self.stack)
        w_ext, w_agr, beta = R.plan(R.distances(leaves, precision), self.f)
        out = []
        for x, idx in zip(leaves, self.index):
            cols = x[(slice(None),) + idx]
            ref, margin = R.apply(w_ext, w_agr, beta, cols, precision)
            scale = np.max(np.abs(np.asarray(cols)), axis=0)
            out.append((np.asarray(ref), np.asarray(margin), scale))
        return out

    def gap(self, got, ref) -> tuple:
        """Largest |got - ref| over the coordinate's scale, and how many
        coordinates were left out as ties."""
        worst, ties = 0.0, 0
        for g, (r, margin, scale) in zip(got, ref):
            keep = margin >= TIE_MARGIN * scale
            ties += int(np.sum(~keep))
            if np.any(keep):
                worst = max(worst, float(np.max(
                    np.abs(g[keep] - r[keep]) / scale[keep])))
        return worst, ties

    def check(self, seed: int) -> dict:
        ref = self.readings("f32")
        worst, ties = self.gap(self.got, ref)
        n = sum(len(g) for g in self.got)
        self.log(f"[agg] compared {n} sampled coordinates over "
                 f"{len(self.got)} leaves, {ties} left out as ties")
        self.ref = ref
        return {"agg_gap": worst}

    def control(self) -> dict:
        """The reference one precision below float32 (``bf16_3x``) in the
        program's place, against itself (call after :meth:`check`)."""
        low = self.readings("bf16_3x")
        return {"agg_gap": self.gap([r for r, _, _ in low], self.ref)[0]}

    def fault_numbers(self) -> dict:
        return {"agg_gap": self.gap(self.got, self.ref)[0]}

    def release(self):
        self.stack = None
