"""Shared kernel utilities."""
from __future__ import annotations

import re

import jax
from jax.extend import core as jex_core


def pick_block(dim: int, pref: int, granule: int = 128) -> int:
    """Largest block <= pref that divides dim, preferring hardware granules.

    Falls back to the full dimension (single block) when no aligned divisor
    exists — correctness over perf for odd shapes; production shapes are
    multiples of 128.
    """
    if dim <= pref:
        return dim
    if dim % pref == 0:
        return pref
    for cand in range(pref - (pref % granule), 0, -granule):
        if dim % cand == 0:
            return cand
    for cand in range(pref, 0, -1):
        if dim % cand == 0:
            return cand
    return dim


def _walk_pallas_calls(jaxpr, out):
    """Collect every ``pallas_call`` equation in ``jaxpr``, recursing through
    call/control-flow sub-jaxprs but NOT into the pallas kernels themselves
    (the boundary is what we audit)."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn)
            continue
        for val in eqn.params.values():
            vals = val if isinstance(val, (list, tuple)) else (val,)
            for v in vals:
                if isinstance(v, jex_core.ClosedJaxpr):
                    _walk_pallas_calls(v.jaxpr, out)
                elif isinstance(v, jex_core.Jaxpr):
                    _walk_pallas_calls(v, out)
    return out


def pallas_calls(closed_jaxpr) -> list[tuple[str, bool]]:
    """``(kernel name, interpret flag)`` of every ``pallas_call`` a traced
    program contains (e.g. ``jax.jit(f).trace(*args).jaxpr``)."""
    return [(str(e.params["name"]), bool(e.params["interpret"]))
            for e in _walk_pallas_calls(closed_jaxpr.jaxpr, [])]


def tpu_kernels_in_hlo(hlo_text: str) -> dict[str, int]:
    """Count the ``tpu_custom_call`` instructions of a compiled TPU program
    (``compiled.as_text()``) per repo kernel name (``panther_*``). A kernel
    run in interpret mode lowers to plain HLO and is not counted, so this is
    the proof that the Mosaic kernel itself is in the program."""
    counts: dict[str, int] = {}
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        m = re.search(r"panther_[a-z_]*[a-z]", line)
        if m:
            counts[m.group(0)] = counts.get(m.group(0), 0) + 1
    return counts


def pallas_input_avals(fn, *args, **kwargs):
    """Abstract-eval ``fn`` and return the list of avals crossing INTO any
    ``pallas_call`` it traces to (HBM-side kernel operands). The audit tool
    behind the no-quantized-operand-crosses-HBM contract of the fused
    DAC/RNG boundary."""
    jaxpr = jax.make_jaxpr(fn)(*args, **kwargs)
    return [v.aval for e in _walk_pallas_calls(jaxpr.jaxpr, []) for v in e.invars]


def forbid_pallas_inputs(fn, *args, forbidden, **kwargs):
    """Assert no pallas_call operand of ``fn(*args, **kwargs)`` matches a
    ``(shape, dtype)`` pair in ``forbidden``, e.g. ``((16, 1024), "int32")``.
    Raises AssertionError listing the offending avals; returns the audited
    aval list on success. Used by tests and the bench gate to prove the
    DAC/RNG fusion: quantized operands, bit planes, and noise grids must not
    exist at the kernel boundary."""
    import numpy as np

    bad = []
    avals = pallas_input_avals(fn, *args, **kwargs)
    norm = {(tuple(s), np.dtype(d).name) for s, d in forbidden}
    for a in avals:
        if (tuple(getattr(a, "shape", ())), np.dtype(getattr(a, "dtype", None)).name) in norm:
            bad.append(a)
    assert not bad, (
        "forbidden array(s) cross the pallas_call boundary (HBM): "
        + ", ".join(str(a) for a in bad)
    )
    return avals
