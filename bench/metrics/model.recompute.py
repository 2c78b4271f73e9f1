"""``model.recompute``: device ms per step of the forward recomputed for the
backward (``rematted_computation`` under ``jvp``, outside the step scopes).
See ``bench/scopes.py``."""
from bench import scopes


def read(ctx):
    return scopes.read(ctx, "recompute")
