"""Jaxpr auditors: statically prove the sharding contracts (DESIGN.md §12).

Each auditor traces a real aggregation path with ``jax.make_jaxpr`` — no
arrays are materialised beyond the eager plan statistics — and walks the
jaxpr (recursing through pjit / shard_map / scan sub-jaxprs) looking for
the exact primitive signature of a shipped or near-missed bug class:

* **C201 apply-shard-gather** — inside the apply ``shard_map`` body the
  only admitted reshard is the worker-row gather of one d-shard: every
  ``all_gather`` must stay ≤ (n_pad, d_pad/M) and must never gather the
  model axis (which would re-materialise full d per device, §3/§10).
* **C202 decode-invariant** — the §9 contract: an encoded wire payload
  (int8/bf16 + per-row multiplier) is dequantized *inside* shard bodies;
  a full-stack narrow→fp32 ``convert_element_type`` outside any shard
  body is the replicated (n, d) fp32 stack the design forbids.
* **C203 tp-reshape-seam** — the §10 blowup signature: a leaf whose
  param dim is constrained to the model axis reaching a rank-reducing
  reshape (``_leaf2d``'s flatten) — GSPMD cannot shard the merged dim
  and silently replicates (the measured 79.8 GB vs 10.4 GB dry-run).
  Taint flows from ``sharding_constraint`` equations (and optional
  explicit invar taint) through elementwise/transpose/broadcast ops to
  any merging reshape.  ``tp_seam_self_test`` proves the auditor is
  live by requiring it to trip on a synthetic tp-pinned leaf.
* **C204 single-compile** — each jitted step must lower exactly once
  per configuration: repeated same-shape calls must add zero backend
  compiles (counted via jax's monitoring events) and leave exactly one
  entry in the trace cache — the regression gate for the PR-2
  baked-trace bug class and for accidental retrace-per-step bugs.
* **C205 hier-decode** — the §11 grouped path decodes per-group row
  slices; a narrow→fp32 convert of the *full* n-row payload outside the
  group loop would defeat the two-level wire budget.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Set

import jax
import jax.numpy as jnp
from jax import monitoring

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_NARROW_DTYPES = ("int8", "uint8", "bfloat16")


@dataclasses.dataclass
class ContractResult:
    contract: str                        # e.g. "C201-apply-shard-gather"
    status: str                          # "proven" | "violated"
    detail: str
    violations: List[str] = dataclasses.field(default_factory=list)

    @property
    def ok(self) -> bool:
        return self.status == "proven"

    def to_json(self) -> dict:
        return dataclasses.asdict(self)


def _result(contract: str, violations: List[str], detail: str
            ) -> ContractResult:
    return ContractResult(
        contract=contract,
        status="violated" if violations else "proven",
        detail=detail, violations=violations)


# ------------------------------------------------------------ jaxpr walking
def _as_open(obj):
    """Unwrap ClosedJaxpr -> Jaxpr; pass Jaxpr through; else None."""
    inner = getattr(obj, "jaxpr", None)
    if inner is not None and hasattr(inner, "eqns"):
        return inner
    if hasattr(obj, "eqns") and hasattr(obj, "invars"):
        return obj
    return None


def _sub_jaxprs(eqn) -> Iterable:
    for v in eqn.params.values():
        for item in (v if isinstance(v, (list, tuple)) else (v,)):
            sub = _as_open(item)
            if sub is not None:
                yield sub


def iter_eqns(jaxpr, in_shard: bool = False):
    """Yield (eqn, in_shard_body) over a jaxpr and all sub-jaxprs."""
    jaxpr = _as_open(jaxpr)
    for eqn in jaxpr.eqns:
        yield eqn, in_shard
        inner = in_shard or eqn.primitive.name == "shard_map"
        for sub in _sub_jaxprs(eqn):
            yield from iter_eqns(sub, inner)


def _axis_names(eqn) -> Sequence[str]:
    ax = eqn.params.get("axis_name", ())
    return ax if isinstance(ax, (tuple, list)) else (ax,)


def _numel(shape) -> int:
    n = 1
    for s in shape:
        n *= int(s)
    return n


# ------------------------------------------------------------------ C201
def gather_violations(closed, *, allowed: int,
                      model_axis: Optional[str]
                      ) -> "tuple[list[str], int]":
    """In-shard all_gather checks shared by C201 and the fixtures."""
    violations, gathers = [], 0
    for eqn, in_shard in iter_eqns(closed):
        if eqn.primitive.name != "all_gather" or not in_shard:
            continue
        gathers += 1
        out = eqn.outvars[0].aval
        if model_axis is not None and model_axis in _axis_names(eqn):
            violations.append(
                f"all_gather over the model axis {model_axis!r} inside "
                f"the apply shard body (output {out.shape}) "
                "re-materialises full d per device")
        elif _numel(out.shape) > allowed:
            violations.append(
                f"all_gather result {out.shape} "
                f"({_numel(out.shape):,} elements) exceeds the per-device "
                f"bound n_pad x d_pad/M = {allowed:,}")
    return violations, gathers


def audit_apply_gather(grads, f: int = 1, rule: str = "multi_bulyan", *,
                       mesh_ctx) -> ContractResult:
    """C201: the apply shard body gathers at most (n_pad, d_pad/M)."""
    from repro.core import api
    agg = api.get_aggregator(rule)
    stats = api.compute_stats(grads, f, needs_dists=agg.needs_dists,
                              mesh_ctx=mesh_ctx)
    agg.validate(stats.n, stats.f)
    plan = agg.plan(stats)
    closed = jax.make_jaxpr(
        lambda g: agg.apply(plan, g, mesh_ctx=mesh_ctx))(grads)

    W, M = mesh_ctx.worker_size, mesh_ctx.model_size
    allowed = 0
    for leaf in jax.tree.leaves(grads):
        n = leaf.shape[0]
        n_pad = -(-n // W) * W
        numel = _numel(leaf.shape[1:])
        d_pad = -(-numel // M) * M
        allowed = max(allowed, n_pad * (d_pad // M))

    violations, gathers = gather_violations(
        closed, allowed=allowed, model_axis=mesh_ctx.model_axis)
    if gathers == 0:
        violations.append("no all_gather found inside a shard body — the "
                          "apply path was not exercised under the mesh")
    return _result(
        "C201-apply-shard-gather", violations,
        f"{gathers} in-shard gather(s) audited against the "
        f"(n_pad, d_pad/M) bound of {allowed:,} elements "
        f"(rule={rule}, mesh W={W} M={M})")


# ------------------------------------------------------------------ C202
def full_stack_decodes(closed, n: int, *, require_in_shard: bool
                        ) -> "tuple[list[str], int]":
    """Narrow→fp32 converts of a full n-row stack, + total decode count."""
    violations, decodes = [], 0
    for eqn, in_shard in iter_eqns(closed):
        if eqn.primitive.name != "convert_element_type":
            continue
        src = eqn.invars[0].aval
        out = eqn.outvars[0].aval
        if str(src.dtype) not in _NARROW_DTYPES \
                or str(out.dtype) != "float32":
            continue
        decodes += 1
        if require_in_shard and in_shard:
            continue
        if len(out.shape) >= 2 and int(out.shape[0]) >= n:
            where = "outside any shard body" if require_in_shard \
                else "over the full worker stack"
            violations.append(
                f"{src.dtype}->{out.dtype} materialisation of the full "
                f"{tuple(int(s) for s in out.shape)} stack {where}")
    return violations, decodes


def audit_decode_invariant(grads, f: int = 1, rule: str = "multi_bulyan", *,
                           mesh_ctx, codec_spec: str = "qsgd:bits=8"
                           ) -> ContractResult:
    """C202: encoded payloads dequantize per shard, never replicated."""
    from repro.comm import codecs as CC
    from repro.core import api
    codec = CC.get_codec(codec_spec)
    enc, _res = codec.encode(grads, key=jax.random.key(0))
    closed = jax.make_jaxpr(
        lambda e: api.aggregate_tree(e, f, rule, mesh_ctx=mesh_ctx))(enc)
    violations, decodes = full_stack_decodes(closed, enc.n,
                                              require_in_shard=True)
    if decodes == 0:
        violations.append(f"no {codec_spec} dequantization found in the "
                          "trace — the encoded path was not exercised")
    return _result(
        "C202-decode-invariant", violations,
        f"{decodes} narrow->fp32 convert(s) audited; all full-stack "
        f"decodes confined to shard bodies (codec={codec_spec}, "
        f"rule={rule})")


# ------------------------------------------------------------------ C203
_ELEMENTWISE_SAFE = True  # same-shape ops propagate taint


def _taint_walk(jaxpr, taint: Dict, model_axis: str,
                violations: List[str]) -> None:
    jaxpr = _as_open(jaxpr)

    def get(v) -> Set[int]:
        if hasattr(v, "val"):           # Literal
            return set()
        return taint.get(v, set())

    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "sharding_constraint":
            spec = getattr(eqn.params.get("sharding"), "spec", None)
            dims = set()
            if spec is not None:
                for i, entry in enumerate(spec):
                    axes = entry if isinstance(entry, tuple) else (entry,)
                    if model_axis in axes:
                        dims.add(i)
            dims |= get(eqn.invars[0])
            if dims:
                taint[eqn.outvars[0]] = dims
            continue
        if name == "shard_map":
            continue                    # explicit layout inside the body
        in_taints = [get(v) for v in eqn.invars]
        if not any(in_taints):
            # still recurse: sub-jaxprs may contain their own constraints
            for sub in _sub_jaxprs(eqn):
                _taint_walk(sub, taint, model_axis, violations)
            continue
        src_idx = next(i for i, t in enumerate(in_taints) if t)
        dims = in_taints[src_idx]
        src = eqn.invars[src_idx].aval
        if name == "reshape":
            out = eqn.outvars[0].aval
            if len(out.shape) != len(src.shape):
                violations.append(
                    f"reshape {tuple(int(s) for s in src.shape)} -> "
                    f"{tuple(int(s) for s in out.shape)} merges dims "
                    f"{sorted(dims)} constrained to the "
                    f"{model_axis!r} axis — GSPMD replicates the merged "
                    "dim (the §10 tp-flatten seam)")
            else:
                taint[eqn.outvars[0]] = dims
            continue
        if name == "transpose":
            perm = eqn.params["permutation"]
            taint[eqn.outvars[0]] = {perm.index(d) for d in dims}
            continue
        if name == "broadcast_in_dim":
            bdims = eqn.params["broadcast_dimensions"]
            taint[eqn.outvars[0]] = {bdims[d] for d in dims
                                     if d < len(bdims)}
            continue
        subs = list(_sub_jaxprs(eqn))
        if subs and len(subs) >= 1:
            for sub in subs:
                sub = _as_open(sub)
                if len(sub.invars) == len(eqn.invars):
                    inner: Dict = {
                        sv: t for sv, t in zip(sub.invars, in_taints) if t}
                    inner_all = dict(taint)
                    inner_all.update(inner)
                    _taint_walk(sub, inner_all, model_axis, violations)
                    for ov, sv in zip(eqn.outvars, sub.outvars):
                        t = inner_all.get(sv) if not hasattr(sv, "val") \
                            else None
                        if t:
                            taint[ov] = t
            continue
        # same-shape ops (elementwise, convert, pad with zero-width...)
        for ov in eqn.outvars:
            if tuple(ov.aval.shape) == tuple(src.shape):
                taint[ov] = dims


def audit_tp_seam(closed, *, model_axis: str = "model",
                  invar_taint: Optional[Dict[int, Set[int]]] = None,
                  label: str = "") -> ContractResult:
    """C203: no rank-reducing reshape of a model-axis-constrained dim."""
    jaxpr = _as_open(closed)
    taint: Dict = {}
    for idx, dims in (invar_taint or {}).items():
        taint[jaxpr.invars[idx]] = set(dims)
    violations: List[str] = []
    _taint_walk(jaxpr, taint, model_axis, violations)
    what = f" ({label})" if label else ""
    return _result(
        "C203-tp-reshape-seam", violations,
        f"taint from sharding_constraint eqns on the {model_axis!r} axis "
        f"propagated to every reshape{what}")


def tp_seam_self_test(model_axis: str = "model") -> ContractResult:
    """The auditor must trip on the synthetic §10 signature.

    A (n, d1, d2) leaf with its last param dim tainted as model-sharded,
    flattened by the exact ``_leaf2d`` reshape — status "proven" here
    means the self-test PASSED (the auditor correctly reported the
    violation); "violated" means the auditor has gone blind.
    """
    leaf = jax.ShapeDtypeStruct((8, 16, 128), jnp.float32)
    closed = jax.make_jaxpr(lambda x: x.reshape(x.shape[0], -1))(leaf)
    res = audit_tp_seam(closed, model_axis=model_axis,
                        invar_taint={0: {2}}, label="self-test")
    tripped = not res.ok
    return ContractResult(
        contract="C203-self-test",
        status="proven" if tripped else "violated",
        detail="auditor trips on a tp-pinned (n, d1, d2) flatten",
        violations=[] if tripped else
        ["auditor failed to flag the synthetic §10 tp-flatten"])


# ------------------------------------------------------------------ C204
class CompileCounter:
    """Counts XLA backend compiles via jax's monitoring events, and keeps
    the name of each compiled program (``jit(<function>)``)."""

    def __init__(self) -> None:
        self.count = 0
        self.names: List[str] = []

    def _listener(self, event: str, duration: float, **kw) -> None:
        if event == _COMPILE_EVENT:
            self.count += 1
            self.names.append(str(kw.get("fun_name")))

    def __enter__(self) -> "CompileCounter":
        monitoring.register_event_duration_secs_listener(self._listener)
        return self

    def __exit__(self, *exc) -> bool:
        monitoring.unregister_event_duration_listener(self._listener)
        return False


def audit_single_compile(fn: Callable, make_args: Callable[[], tuple], *,
                         label: str, repeats: int = 2) -> ContractResult:
    """C204: a jitted step lowers once; identical calls hit the cache.

    ``fn`` must be the jitted callable itself (so its trace cache can be
    inspected); ``make_args`` returns fresh same-shape arguments per
    call.
    """
    with CompileCounter() as warm:
        fn(*make_args())
    with CompileCounter() as rest:
        for _ in range(repeats):
            fn(*make_args())
    cache = fn._cache_size() if hasattr(fn, "_cache_size") else None
    violations = []
    if rest.count > 0:
        violations.append(
            f"{label}: {rest.count} backend compile(s) on {repeats} "
            "repeated identical-shape calls — the step retraces")
    if cache is not None and cache != 1:
        violations.append(
            f"{label}: trace cache holds {cache} entries after "
            "identical-config calls (want exactly 1)")
    return _result(
        "C204-single-compile", violations,
        f"{label}: {warm.count} compile(s) on first call, {rest.count} on "
        f"{repeats} repeats, cache size {cache}")


# ------------------------------------------------------------------ C205
def audit_hier_decode(grads, f: int = 1, spec: str = "g=7",
                      rule: str = "multi_bulyan",
                      codec_spec: str = "qsgd:bits=8") -> ContractResult:
    """C205: the grouped path decodes per-group slices, never full-n."""
    from repro.comm import codecs as CC
    from repro.hier import GroupConfig, hier_aggregate_tree
    codec = CC.get_codec(codec_spec)
    enc, _res = codec.encode(grads, key=jax.random.key(0))
    cfg = GroupConfig.from_spec(spec, rule=rule)
    closed = jax.make_jaxpr(
        lambda e: hier_aggregate_tree(e, f, cfg)[0])(enc)
    violations, decodes = full_stack_decodes(closed, enc.n,
                                              require_in_shard=False)
    if decodes == 0:
        violations.append("no dequantization found in the grouped trace")
    return _result(
        "C205-hier-decode", violations,
        f"{decodes} narrow->fp32 convert(s) audited; every decode is a "
        f"per-group row slice (< n={enc.n} rows; {spec}, "
        f"codec={codec_spec})")
