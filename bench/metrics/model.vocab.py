"""``model.vocab``: device ms per step of the vocabulary-wide work (the
``lm.embed`` and ``lm.head`` scopes of ``models/lm.py``) in every phase: the
embedding gather and its gradient, the head both ways and its recompute.
See ``bench/scopes.py``."""
from bench import scopes


def read(ctx):
    return scopes.read(ctx, "vocab")
