"""Share of the HBM roofline that the round's stats kernels
(``pairwise_stats``, ``pairwise_stats_rect``) reach."""
from metrics._scopes import named_roofline


def read(ctx):
    return named_roofline(ctx, r"pairwise_stats(_rect)?")
