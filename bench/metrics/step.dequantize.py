"""``step.dequantize``: device ms per step of the plane dequantize (the
``step.dequantize`` scope of ``train/step.py``: ``panther.materialize_split``
and ``panther.operandize``). See ``bench/scopes.py``."""
from bench import scopes


def read(ctx):
    return scopes.read(ctx, "dequantize")
