"""Share of the HBM roofline that the round's ``fused_select`` kernels
reach."""
from metrics._scopes import named_roofline


def read(ctx):
    return named_roofline(ctx, r"fused_select")
