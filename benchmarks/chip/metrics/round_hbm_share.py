"""The least bytes a round needs (the float32 stack read twice, the
aggregate written once) at the chip's peak HBM bandwidth, over the time a
round took in the traced window."""


def read(ctx):
    if not ctx.units or ctx.least_bytes_per_unit is None:
        return None
    return 100.0 * ctx.least_bytes_per_unit * ctx.units / ctx.window_s \
        / ctx.peaks["hbm_bytes_per_s"]
