"""Device time per step under ``robust.stats``, ``robust.plan`` and
``robust.apply``: the step's aggregation, in ms."""
from metrics._scopes import scope_ms


def read(ctx):
    return scope_ms(ctx, "stats", "plan", "apply")
