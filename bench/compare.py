"""The comparison that decides ``correct``.

Both sides give the same readings of the first steps of a run, all made on
the state that the next step reads:

* ``loss``: the loss of each step;
* ``change1``: per leaf, ``||w_1 - w_0||``, the first update: over the
  learning rate, the norm of the first gradient as the optimizer got it;
* ``change``: per leaf, ``||w_k - w_0||`` after the last compared step;
* ``frac_bits``: per crossbar leaf, the exponent of its weight grid.

A leaf's gap is ``|prog - ref| / max(ref, median ref over leaves)``, and the
worst leaf counts; leaves whose reference reading is under a thousandth of
the median are left out (their update is rounding alone).
"""
from __future__ import annotations

import math
import statistics

NEGLIGIBLE = 1e-3
NUMBERS = ("loss_gap", "grad1_gap", "change_gap", "frac_bits_off")


def leaf_gap(prog: dict, ref: dict) -> tuple:
    """-> (worst gap, its leaf, leaves left out)."""
    med = statistics.median(ref.values())
    worst, leaf, skipped = 0.0, None, []
    for p, r in sorted(ref.items()):
        if r < NEGLIGIBLE * med:
            skipped.append(p)
            continue
        g = abs(prog[p] - r) / max(r, med)
        if not math.isfinite(g) or g > worst:
            worst, leaf = g, p
            if not math.isfinite(g):
                break
    return worst, leaf, skipped


def compare(prog: dict, ref: dict, limits: dict) -> tuple:
    """-> (correct, numbers, detail): ``numbers[name] = {"value", "limit"}``
    for each number the cell's limits hold, in the order of ``NUMBERS``. A
    limit of None marks a number the cell does not compare (its readings
    cannot separate a sound run from the control; see PERF.md); it is
    reported in ``detail``. A cell without limits is never correct."""
    loss_gap = max(abs(a - b) for a, b in zip(prog["loss"], ref["loss"]))
    if not all(math.isfinite(v) for v in prog["loss"]):
        loss_gap = math.inf
    grad1, grad1_leaf, _ = leaf_gap(prog["change1"], ref["change1"])
    change, change_leaf, skipped = leaf_gap(prog["change"], ref["change"])
    off = sum(prog["frac_bits"][p] != f for p, f in ref["frac_bits"].items())
    values = {"loss_gap": loss_gap, "grad1_gap": grad1, "change_gap": change, "frac_bits_off": off}
    numbers = {k: {"value": values[k], "limit": limits[k]} for k in NUMBERS if limits.get(k) is not None}
    correct = bool(numbers) and all(math.isfinite(n["value"]) and n["value"] <= n["limit"]
                                    for n in numbers.values())
    detail = {"grad1_leaf": grad1_leaf, "change_leaf": change_leaf, "left_out": skipped,
              "not_compared": {k: values[k] for k in NUMBERS if k not in numbers}}
    return correct, numbers, detail
