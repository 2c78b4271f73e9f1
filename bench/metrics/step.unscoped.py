"""``step.unscoped``: device ms per step that no phase claims (instructions
outside every scope and transform, and events of no instruction of the step);
with the five phases it sums to the window's op time per step. See
``bench/scopes.py``."""
from bench import scopes


def read(ctx):
    return scopes.read(ctx, "unscoped")
