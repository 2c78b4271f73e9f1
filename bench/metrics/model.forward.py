"""``model.forward``: device ms per step of the forward pass (instructions
under ``jvp`` and outside the step scopes, not recomputed). See
``bench/scopes.py``."""
from bench import scopes


def read(ctx):
    return scopes.read(ctx, "forward")
