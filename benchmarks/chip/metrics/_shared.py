"""Arithmetic shared by the per-layer readers.  Each reader returns None
where the trace holds nothing for it to read."""
from __future__ import annotations

import trace_reduce as T


def pallas_roofline(ctx):
    """Bytes every Pallas call (``tpu_custom_call``) reads and writes, from
    its operand and result shapes in the compiled HLO, at the chip's peak
    HBM bandwidth, over those calls' summed device time: the calls run at
    a few FLOPs per byte, far under the chip's ridge, so bytes bound them."""
    nbytes, ns = T.kernel_bytes_and_ns(ctx.trace, ctx.dev, ctx.kernel_bytes)
    if ns <= 0:
        return None
    return 100.0 * nbytes / ctx.peaks["hbm_bytes_per_s"] / (ns * 1e-9)


def idle_share(ctx):
    if not ctx.trace.devices.get(ctx.dev):
        return None
    return 100.0 * T.idle_share(ctx.trace, ctx.dev)


def flops_share(ctx):
    """FLOPs the units of the traced window required, over the window and
    the chips' bf16 peak."""
    if not ctx.units or ctx.flops_per_unit is None:
        return None
    return 100.0 * ctx.flops_per_unit * ctx.units / ctx.window_s \
        / (ctx.chips * ctx.peaks["bf16_flops_per_s"])
