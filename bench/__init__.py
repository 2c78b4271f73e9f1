"""Chip benchmark of the PANTHER train step (``python3 bench/run.py``).

Everything here is the yardstick: the weight and token generators, the plain
reference of the step, the comparison that decides ``correct``, the trace
reduction, the peak table and the per-kernel work counts. The program under
test (``src/repro``) is imported only by ``run.py`` and ``control.py``.
"""
