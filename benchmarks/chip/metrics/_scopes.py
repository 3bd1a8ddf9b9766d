"""Device time per phase of the program, and the rooflines of its kernels
by name, for the per-layer readers.

Each device event's phase is read from the ``op_name`` of its instruction
in the programs loaded in this process (``scope_reduce.live_programs``),
where the program's ``robust.*`` scopes are; a window that ran no program
carrying them gives nothing to read.  The reduction runs once per traced
run and is logged: a ``[bench] scopes`` line (ms per unit of each phase,
``unscoped`` and ``other programs``, their sum and the busy time), the
unscoped instructions with the most time, and each kernel's calls, bytes
and time.
"""
from __future__ import annotations

import json
import re

import scope_reduce as S
import trace_reduce as T

#: the last traced run's context and its reduction
_memo: list = []


def phases_ms(ctx):
    """``{phase or part: ms per unit}`` of the traced window, or None."""
    if _memo and _memo[0][0] is ctx:
        return _memo[0][1]
    out = _reduce(ctx)
    _memo[:] = [(ctx, out)]
    return out


def _reduce(ctx):
    if not ctx.units or not ctx.trace.devices.get(ctx.dev):
        return None
    import jax
    programs = [ops for _, ops in S.live_programs(jax)]
    parts = S.attribute(ctx.trace, ctx.dev, programs)
    if all(part == S.OTHER for _, _, part, _ in parts):
        return None
    per = {k: ns * 1e-6 / ctx.units for k, ns in S.phase_ns(parts).items()}
    busy = T.busy_ns(ctx.trace, ctx.dev) * 1e-6 / ctx.units
    print("[bench] scopes " + json.dumps(
        {**per, "sum": sum(per.values()), "busy": busy}), flush=True)
    print("[bench] unscoped ops " + json.dumps(S.unscoped_ops(parts)),
          flush=True)
    print("[bench] kernels " + json.dumps(S.kernels_by_name(
        ctx.trace, ctx.dev, ctx.kernel_bytes)), flush=True)
    return per


def scope_ms(ctx, *phases):
    """Device self time per unit under the given phases, in ms."""
    per = phases_ms(ctx)
    if per is None:
        return None
    return sum(per.get(p, 0.0) for p in phases)


def named_roofline(ctx, kernel: str):
    """``_shared.pallas_roofline``'s arithmetic on the Pallas calls whose
    kernel name matches the pattern ``kernel``."""
    picked = {k: v for k, v in ctx.kernel_bytes.items()
              if re.fullmatch(kernel, S.kernel_name(k))}
    nbytes, ns = T.kernel_bytes_and_ns(ctx.trace, ctx.dev, picked)
    if ns <= 0:
        return None
    return 100.0 * nbytes / ctx.peaks["hbm_bytes_per_s"] / (ns * 1e-9)
