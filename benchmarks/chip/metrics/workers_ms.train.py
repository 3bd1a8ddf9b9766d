"""Device time per step under ``robust.workers``: the n workers' forward
and backward passes (``vmap(value_and_grad)``), in ms."""
from metrics._scopes import scope_ms


def read(ctx):
    return scope_ms(ctx, "workers")
