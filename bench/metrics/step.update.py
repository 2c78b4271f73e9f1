"""``step.update``: device ms per step of the update (the ``step.update``
scope of ``train/step.py``: the learning rate, ``panther.update_split`` with
the fused OPA, ``opa_deposit`` and the CRS ``cond``, and the gradient norm).
See ``bench/scopes.py``."""
from bench import scopes


def read(ctx):
    return scopes.read(ctx, "update")
