"""Device time per round of every operation that is not a Pallas call:
the XLA substrate, in milliseconds.  Taken as the chip's busy time (the
union of its operation intervals) less the Pallas calls' time, since a
loop's event on the trace spans the events of its body."""
import trace_reduce as T


def read(ctx):
    if not ctx.units or not ctx.trace.devices.get(ctx.dev):
        return None
    _, kernel_ns = T.kernel_bytes_and_ns(ctx.trace, ctx.dev,
                                         ctx.kernel_bytes)
    return (T.busy_ns(ctx.trace, ctx.dev) - kernel_ns) * 1e-6 / ctx.units
