"""Kernel profiling hooks: what did each Pallas launch actually choose?

The ``kernels/ops.py`` public wrappers resolve their launch config —
autotuned ``d_tile``, grid depth, deep-grid lift — in Python, *outside*
jit, immediately before calling the jitted privates.  That resolution
point is the hook: with a :class:`KernelProfiler` installed, each
wrapper calls :func:`record_kernel` and the profiler captures one
:class:`KernelRecord` per launch config, pairing the chosen tile with
the ``analysis/vmem.py`` prediction for exactly that tile (closing the
loop between the §12 cost model and the live launches).

Two honesty notes, both load-bearing:

* on the hot path (wrappers called inside a jitted step) records fire
  at **trace time** — one record per distinct launch shape, not one per
  call; a shape that hits jax's compilation cache produces no new
  record.  That is the right granularity for a *static* launch config,
  and the reason the hook costs nothing per step.  Eager wrapper calls
  record once per call;
* ``vmem_measured`` comes from XLA's ``memory_analysis()`` on a real
  compile (:func:`measure_vmem`) and is ``None`` where the backend does
  not report it (CPU interpret mode) — predicted-vs-measured is only
  claimed where both numbers exist.

No profiler installed (the default) → :func:`record_kernel` returns
after one tuple check; the wrappers stay allocation-free.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

_ACTIVE: List["KernelProfiler"] = []


@dataclasses.dataclass(frozen=True)
class KernelRecord:
    """One distinct kernel launch config, with its vmem prediction."""

    kernel: str              # fused_select | pairwise_stats | dequant_stats
    n: int                   # stack rows (unpadded)
    d: int
    d_tile: int              # inner compute window the wrapper launched with
    macro_tile: int          # outer macro block (== d_tile -> single-level)
    grid_steps: int          # OUTER grid steps (macro blocks)
    windows: int             # inner d_tile windows per macro block
    vmem_predicted: Optional[int]   # analysis/vmem per-step working set
    vmem_budget: Optional[int]
    over_budget: Optional[bool]
    vmem_measured: Optional[int] = None   # XLA memory_analysis, if any

    def to_json(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


class KernelProfiler:
    """Installable sink for wrapper launch records (context manager)."""

    def __init__(self):
        self.records: List[KernelRecord] = []

    def __enter__(self) -> "KernelProfiler":
        _ACTIVE.append(self)
        return self

    def __exit__(self, *exc) -> None:
        _ACTIVE.remove(self)


def record_kernel(kernel: str, *, n: int, d: int, d_tile: int,
                  macro_tile: Optional[int] = None,
                  theta: Optional[int] = None,
                  dtype: Optional[str] = None,
                  n_loc: Optional[int] = None) -> None:
    """Called by the ops wrappers after tile resolution; cheap no-op
    unless a profiler is installed."""
    if not _ACTIVE:
        return
    macro = d_tile if macro_tile is None else macro_tile
    est = _predict(kernel, n=n, d=d, d_tile=d_tile, macro_tile=macro,
                   theta=theta, dtype=dtype)
    rec = KernelRecord(
        kernel=kernel, n=n, d=d, d_tile=d_tile, macro_tile=macro,
        grid_steps=-(-d // macro), windows=macro // d_tile,
        vmem_predicted=None if est is None else est.vmem_bytes,
        vmem_budget=None if est is None else est.vmem_budget,
        over_budget=None if est is None else est.over_budget)
    for profiler in _ACTIVE:
        profiler.records.append(rec)


def _predict(kernel: str, *, n: int, d: int, d_tile: int, macro_tile: int,
             theta: Optional[int], dtype: Optional[str]):
    # lazy import: vmem imports kernels.ops at module load, and ops
    # imports this module — resolving the estimate at record time keeps
    # the cycle open
    from repro.analysis import vmem
    try:
        if kernel == "fused_select":
            if theta is None or (n - theta - 2) % 2:
                return None
            return vmem.estimate_fused_select(
                n, d, f=(n - theta - 2) // 2, d_tile=d_tile,
                macro_tile=macro_tile)
        if kernel == "pairwise_stats":
            return vmem.estimate_pairwise_stats(
                n, d, d_tile=d_tile, macro_tile=macro_tile)
        if kernel == "dequant_stats":
            return vmem.estimate_dequant_stats(
                n, d, dtype=dtype or "int8", d_tile=d_tile,
                macro_tile=macro_tile)
    except ValueError:
        return None
    return None


def measure_vmem(fn, *args, **kwargs) -> Optional[int]:
    """Compile ``fn(*args, **kwargs)`` and ask XLA for its temp bytes.

    Returns ``None`` when the backend's ``memory_analysis()`` is missing
    or unpopulated (CPU) — absence of a measurement is reported as
    absence, never as zero.
    """
    import jax
    try:
        compiled = jax.jit(fn).lower(*args, **kwargs).compile()
        mem = compiled.memory_analysis()
        if mem is None:
            return None
        size = getattr(mem, "temp_size_in_bytes", None)
        return None if size is None else int(size)
    except Exception:
        return None


def profile_points(points, *, f_fn=None) -> List[Dict[str, Any]]:
    """Run the three stats/apply kernels at given (n, d) points under a
    profiler and return record dicts with measured VMEM attached where
    the backend reports it.  Used by ``launch/obs_report.py --kernels``.
    """
    import jax.numpy as jnp
    import numpy as np

    from repro.analysis import vmem
    from repro.kernels import ops

    out: List[Dict[str, Any]] = []
    for n, d in points:
        f = vmem.f_for_bench(n) if f_fn is None else f_fn(n)
        theta = n - 2 * f - 2
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
        w = jnp.asarray(rng.random((theta, n)), jnp.float32)
        payload = jnp.asarray(
            rng.integers(-127, 127, size=(n, d)), jnp.int8)
        mult = jnp.ones((n,), jnp.float32)
        with KernelProfiler() as prof:
            ops.pairwise_stats(x)
            ops.dequant_stats(payload, mult)
            ops.fused_select(x, w, w, beta=max(theta - 2 * f, 1))
        measured = {
            "pairwise_stats": measure_vmem(lambda a: ops.pairwise_stats(a),
                                           x),
            "dequant_stats": measure_vmem(
                lambda p, m: ops.dequant_stats(p, m), payload, mult),
            "fused_select": measure_vmem(
                lambda a, b, c: ops.fused_select(
                    a, b, c, beta=max(theta - 2 * f, 1)), x, w, w),
        }
        for rec in prof.records:
            out.append(dataclasses.replace(
                rec, vmem_measured=measured.get(rec.kernel)).to_json())
    return out
