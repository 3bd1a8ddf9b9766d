"""Device time per round under ``robust.apply``: the coordinate select of
every leaf, Pallas and XLA alike, in ms."""
from metrics._scopes import scope_ms


def read(ctx):
    return scope_ms(ctx, "apply")
