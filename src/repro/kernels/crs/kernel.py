"""Pallas TPU kernel for the Carry Resolution Step (paper §3.2).

In the accelerator, CRS is the *expensive* serial read-propagate-write pass
that PANTHER amortizes to every ~1024 steps. On TPU it is a cheap in-place
elementwise pass over the digit planes: digit-serial carry propagation
(LSB->MSB, small ints only), then railing at the canonical limit via an
MSB-first lexicographic compare — one VMEM round trip per plane tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.slicing import LOGICAL_BITS, RADIX, SliceSpec
from repro.kernels.common import pick_block

DEFAULT_BM = 256
DEFAULT_BN = 256


def _digits_of(value: int, n: int) -> list:
    out = []
    rem = value
    for _ in range(n):
        d = ((rem + RADIX // 2) % RADIX) - RADIX // 2
        out.append(d)
        rem = (rem - d) // RADIX
    return out


def _crs_kernel(planes_ref, out_ref, *, spec: SliceSpec):
    S = spec.n_slices
    # digit-serial carry propagation (all int32, TPU-safe)
    carry = jnp.zeros(planes_ref.shape[1:], jnp.int32)
    digs = []
    for s in range(S):
        v = planes_ref[s].astype(jnp.int32) + carry
        d = ((v + RADIX // 2) & (RADIX - 1)) - RADIX // 2
        digs.append(d)
        carry = jax.lax.shift_right_arithmetic(v - d, LOGICAL_BITS)

    lim = spec.canonical_limit
    pos_rail = _digits_of(lim, S)
    neg_rail = _digits_of(-lim, S)

    # values below -lim are carry-free but out of range: rail them via an
    # MSB-first lexicographic compare against the -lim digit vector
    lt = jnp.zeros(planes_ref.shape[1:], bool)
    gt = jnp.zeros(planes_ref.shape[1:], bool)
    for s in range(S - 1, -1, -1):
        d, r = digs[s], neg_rail[s]
        lt_new = lt | (~gt & (d < r))
        gt = gt | (~lt & (d > r))
        lt = lt_new
    lt = lt & (carry == 0)  # carry-out rails take precedence (match ref order)

    for s in range(S):
        d = digs[s]
        d = jnp.where(carry > 0, pos_rail[s], d)
        d = jnp.where(carry < 0, neg_rail[s], d)
        d = jnp.where(lt, neg_rail[s], d)
        out_ref[s] = d.astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("spec", "bm", "bn", "interpret"))
def crs(
    planes: jax.Array,
    *,
    spec: SliceSpec,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    interpret: bool = False,
) -> jax.Array:
    """planes int8 [S,M,N] -> canonical planes, one fused in-place pass."""
    S, M, N = planes.shape
    assert S == spec.n_slices
    bm, bn = pick_block(M, bm), pick_block(N, bn)
    return pl.pallas_call(
        functools.partial(_crs_kernel, spec=spec),
        grid=(M // bm, N // bn),
        in_specs=[pl.BlockSpec((S, bm, bn), lambda i, j: (0, i, j))],
        out_specs=pl.BlockSpec((S, bm, bn), lambda i, j: (0, i, j)),
        out_shape=jax.ShapeDtypeStruct(planes.shape, jnp.int8),
        input_output_aliases={0: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
        name="panther_crs",
    )(planes)
