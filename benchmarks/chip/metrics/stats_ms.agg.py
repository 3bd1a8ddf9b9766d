"""Device time per round under ``robust.stats``: the pairwise distances
and norms, in ms."""
from metrics._scopes import scope_ms


def read(ctx):
    return scope_ms(ctx, "stats")
