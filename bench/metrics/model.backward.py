"""``model.backward``: device ms per step of the backward pass (instructions
under ``transpose(jvp...)``, outside the step scopes, not recomputed). See
``bench/scopes.py``."""
from bench import scopes


def read(ctx):
    return scopes.read(ctx, "backward")
