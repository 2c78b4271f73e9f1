"""Pallas TPU kernels for bit-sliced OPA (the paper's §3 on the MXU/VPU).

Two entry points:

``opa_deposit``  — reads an int32 grid-quantized update block and the S digit
                   planes, performs the balanced base-16 decompose + per-plane
                   saturating accumulate entirely in VMEM, writes planes back
                   (aliased in-place). One HBM pass over planes + update.

``opa_fused``    — the TPU-native analogue of in-crossbar OPA: computes the
                   gradient outer product ``X^T @ dH`` on the MXU, tile by
                   tile, and deposits straight into the digit planes. The
                   full-precision gradient matrix **never exists in HBM** —
                   this is the memory-roofline win corresponding to the
                   paper's elimination of serial crossbar reads/writes.

In-kernel stochastic rounding: with ``rkey`` set, the rounding noise is
generated inside the kernel at GLOBAL element coordinates — each (i, j) grid
tile derives its sub-window from ``program_id`` offsets, so the U[0, 1)
value at logical element (r, c) is a pure function of (r, c) and the two
int32 key words, independent of blocking. ``rng_impl="counter"`` uses the
murmur3-fmix32 coordinate hash shared with ``core.fixed_point
.counter_uniform`` (bit-identical to the jnp reference and to any block
shape); ``rng_impl="hw"`` seeds the TPU hardware PRNG per tile from the key
words mixed with the linear tile id (fastest; not coordinate-stable across
blockings; TPU-only). The legacy ``noise`` grid input remains as the
``rng_mode="grid"`` escape hatch for replaying PR1–5 runs — it ships an
[M, N] f32 array through HBM on the hottest write path, which the keyed
modes exist to eliminate (audited by ``kernels.common.forbid_pallas_inputs``).

Blocking: planes are [S, bm, bn] per grid cell (S is a small leading dim —
all slices of a tile co-reside in VMEM, like the S crossbars of one MCU).
bm/bn default to 128/256: int8 native tile is (32, 128); f32 accumulate tile
(8, 128); the MXU contraction dim inside ``opa_fused`` is ``bt=512``.
VMEM budget at defaults: planes 8·128·256 int8 = 256 KiB + acc f32 128 KiB +
x/dh blocks 512·(128+256)·4 B = 768 KiB ≈ 1.2 MiB « 16 MiB VMEM.

Non-ideal device physics (``dev``, a ``models.common.DeviceModel``): the
fused deposit is where conductance writes happen, so the write-path
non-idealities enter ``opa_fused``'s finalize, in physical order — update
asymmetry (``asym_up``/``asym_down`` gains applied to the signed analog
increment), Gaussian conductance write noise (``write_noise`` sigma in
weight-grid LSBs, drawn in-kernel by the same counter-hash discipline as
stochastic rounding but from an independent key stream — no noise grid
crosses HBM), then the grid rounding, then the digit deposit, and finally
the static stuck-cell mask (``stuck_frac``/``stuck_seed``): stuck cells keep
their pre-update digit, so subsequent reads of the same planes see the fault
consistently. ``dev=None`` compiles the exact pre-DeviceModel kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.slicing import LOGICAL_BITS, SliceSpec
from repro.kernels.common import pick_block

_RADIX_MASK = (1 << LOGICAL_BITS) - 1  # 15
_HALF = 1 << (LOGICAL_BITS - 1)  # 8

DEFAULT_BM = 128
DEFAULT_BN = 256
DEFAULT_BT = 512


def _deposit(planes_i32, rem, spec: SliceSpec):
    """Shared digit-decompose + saturating-add body. planes_i32 [S,bm,bn]."""
    lim = spec.canonical_limit
    rem = jnp.clip(rem, -lim, lim)  # beyond-canonical updates rail (match ref)
    outs = []
    for s in range(spec.n_slices):
        d = ((rem + _HALF) & _RADIX_MASK) - _HALF  # balanced digit in [-8, 7]
        m = spec.plane_max[s]
        outs.append(jnp.clip(planes_i32[s] + d, -m, m))
        # (rem - d) is an exact multiple of 16 -> arithmetic shift is exact.
        rem = jax.lax.shift_right_arithmetic(rem - d, LOGICAL_BITS)
    return jnp.stack(outs, axis=0).astype(jnp.int8)


def _opa_deposit_kernel(p_ref, planes_ref, out_ref, *, spec: SliceSpec):
    rem = p_ref[...]
    out_ref[...] = _deposit(planes_ref[...].astype(jnp.int32), rem, spec)


@functools.partial(jax.jit, static_argnames=("spec", "bm", "bn", "interpret"))
def opa_deposit(
    planes: jax.Array,
    p_q: jax.Array,
    *,
    spec: SliceSpec,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    interpret: bool = False,
) -> jax.Array:
    """planes int8 [S,M,N]; p_q int32 [M,N] on the weight grid -> new planes."""
    S, M, N = planes.shape
    assert S == spec.n_slices
    bm, bn = pick_block(M, bm), pick_block(N, bn)
    grid = (M // bm, N // bn)
    return pl.pallas_call(
        functools.partial(_opa_deposit_kernel, spec=spec),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((S, bm, bn), lambda i, j: (0, i, j)),
        ],
        out_specs=pl.BlockSpec((S, bm, bn), lambda i, j: (0, i, j)),
        out_shape=jax.ShapeDtypeStruct(planes.shape, jnp.int8),
        input_output_aliases={1: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
        name="panther_opa_deposit",
    )(p_q, planes)


def _block_noise(rng: str, k0, k1, i, j, tid, block_shape):
    """In-kernel U[0, 1) block for stochastic rounding at GLOBAL element
    coordinates (program-id block offsets ``i``/``j`` + iotas), so the draw
    is identical for any bm/bn blocking.

    ``rng="counter"`` — the stateless int32 coordinate hash shared with
    ``core.fixed_point.counter_uniform``: bit-identical to the jnp reference
    (and the dense-pipeline ``quantize``) in compiled and interpret mode.

    ``rng="hw"`` — the TPU hardware PRNG (``pltpu.prng_random_bits``), seeded
    per (i, j) tile from the two prefetched key words mixed with the linear
    tile id. Highest throughput on real hardware, but the bit stream is not
    reproducible against the CPU reference (and the interpreter has no
    lowering for it) — an opt-in for TPU runs that don't replay checkpoints.
    """
    from repro.core.fixed_point import _fmix32, _U24, counter_u01

    bm, bn = block_shape
    if rng == "counter":
        r = i * bm + jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 0)
        c = j * bn + jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 1)
        return counter_u01(r, c, k0, k1)
    assert rng == "hw", rng
    pltpu.prng_seed(_fmix32(k0 ^ _fmix32(k1 ^ tid)))
    bits = pltpu.prng_random_bits((bm, bn))
    return jax.lax.shift_right_logical(bits, 8).astype(jnp.float32) * jnp.float32(_U24)


def _global_coords(i, j, block_shape):
    """Global (row, col) iota grids for the (i, j) tile of a blocked array —
    the coordinate frame every counter-hash draw is keyed on."""
    bm, bn = block_shape
    r = i * bm + jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 0)
    c = j * bn + jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 1)
    return r, c


def _stuck_masks(dev, spec, i, j, block_shape):
    """Static per-slice stuck-cell masks [S, bm, bn] at global coordinates.

    Keyed only by ``(stuck_seed, slice)`` — a compile-time pattern
    (fabrication defects don't move between steps, and the jnp reference
    reproduces it exactly). The pattern is shared across lax.scan layer
    stacks (one trace serves every layer); per-layer fault maps need
    per-leaf seeds."""
    from repro.core.fixed_point import counter_u01, device_pattern_words

    r, c = _global_coords(i, j, block_shape)
    frac = jnp.float32(dev.stuck_frac)
    masks = []
    for s in range(spec.n_slices):
        w0, w1 = device_pattern_words(dev.stuck_seed, s)
        masks.append(counter_u01(r, c, jnp.int32(w0), jnp.int32(w1)) < frac)
    return jnp.stack(masks, axis=0)


def _opa_fused_kernel(
    scale_ref, x_ref, dh_ref, planes_ref, *rest,
    spec: SliceSpec, nk: int, rng: str | None, dev=None,
):
    rest = list(rest)
    noise_ref = key_ref = dkey_ref = None
    if rng == "grid":
        noise_ref = rest.pop(0)
    elif rng is not None:
        key_ref = rest.pop(0)
    if dev is not None and dev.write_noise > 0.0:
        dkey_ref = rest.pop(0)
    out_ref, acc_ref = rest
    # program ids are read at top level (the interpret-mode evaluator only
    # substitutes them outside sub-jaxprs) and closed over by _finalize
    i = pl.program_id(0)
    j = pl.program_id(1)
    tid = i * pl.num_programs(1) + j
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # MXU contraction over this token tile: [bm, bt] x [bt, bn]. HIGHEST
    # (f32 contraction), as in the references: a single bf16 pass would
    # round the f32 operands before the outer product.
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...],
        dh_ref[...],
        (((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == nk - 1)
    def _finalize():
        lim = float(2**31 - 1)
        # (acc * scale) * 2^F: the rounding of the dense path's
        # quantize(-lr * g, F); the second factor is an exact power of two,
        # so a fused multiply-add into the rounding below cannot change y
        y = acc_ref[...] * scale_ref[0, 0] * scale_ref[0, 1]
        if dev is not None and (dev.asym_up != 1.0 or dev.asym_down != 1.0):
            # asymmetric potentiation/depression: gain depends on the sign of
            # the analog increment, before it quantizes to the grid
            y = jnp.where(
                y >= 0.0, y * jnp.float32(dev.asym_up), y * jnp.float32(dev.asym_down)
            )
        if dkey_ref is not None:
            # conductance write noise, generated in-kernel at global element
            # coordinates from its own prefetched key words (independent of
            # the rounding stream) — no noise grid crosses HBM
            from repro.core.fixed_point import counter_gauss

            r, c = _global_coords(i, j, acc_ref.shape)
            y = y + jnp.float32(dev.write_noise) * counter_gauss(
                r, c, dkey_ref[0, 0], dkey_ref[0, 1]
            )
        if rng == "grid":
            # legacy escape hatch: U[0, 1) fed as a grid-shaped HBM input
            # (the PR 1-5 draw — kept so old checkpoints replay bit-exactly)
            y = jnp.floor(y + noise_ref[...])
        elif rng is not None:
            # unbiased stochastic rounding with the noise GENERATED IN-KERNEL
            # from the two prefetched key words — no grid array crosses HBM
            y = jnp.floor(
                y + _block_noise(rng, key_ref[0, 0], key_ref[0, 1], i, j, tid, acc_ref.shape)
            )
        else:
            y = jnp.round(y)
        p_q = jnp.clip(y, -lim, lim).astype(jnp.int32)
        new = _deposit(planes_ref[...].astype(jnp.int32), p_q, spec)
        if dev is not None and dev.stuck_frac > 0.0:
            # stuck cells keep their pre-update digit (reads stay consistent:
            # the planes remain the single physical truth)
            new = jnp.where(_stuck_masks(dev, spec, i, j, acc_ref.shape),
                            planes_ref[...], new)
        out_ref[...] = new


@functools.partial(
    jax.jit, static_argnames=("spec", "bm", "bn", "bt", "interpret", "rng_impl", "dev")
)
def opa_fused(
    planes: jax.Array,
    x: jax.Array,
    dh: jax.Array,
    scale: jax.Array,
    *,
    spec: SliceSpec,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    bt: int = DEFAULT_BT,
    interpret: bool = False,
    noise: jax.Array | None = None,
    rkey: jax.Array | None = None,
    rng_impl: str = "counter",
    dev=None,
    dkey: jax.Array | None = None,
    grid_scale: jax.Array | float = 1.0,
) -> jax.Array:
    """Fused ``planes <- deposit(planes, q(X^T dH * scale * grid_scale))``.

    planes int8 [S,M,N]; x [T,M]; dh [T,N] (``-lr`` folded by caller into
    ``scale``); scale f32 scalar (±lr, or ±lr·2**F with ``grid_scale=1``);
    ``grid_scale`` f32 power of two ``2**F`` applied after ``scale`` — the
    same two roundings as ``quantize(-lr * g, F)``. Stochastic rounding
    options:

    * ``rkey`` int32 ``[2]`` key words — the noise is generated **inside the
      kernel** at global element coordinates (``rng_impl="counter"``, the
      reproducible coordinate hash; ``"hw"`` the TPU hardware PRNG). Only two
      scalars cross into SMEM; neither the gradient nor any noise grid
      touches HBM.
    * ``noise`` f32 [M,N] in [0, 1) — legacy grid input (``rng_mode="grid"``
      upstream), kept for bit-exact replay of PR 1-5 checkpoints.

    ``dev`` (a jit-static ``models.common.DeviceModel``) turns on the
    write-path non-idealities in the finalize (see module docstring);
    ``dkey`` int32 ``[2]`` supplies the write-noise key words through a
    second SMEM prefetch when ``dev.write_noise > 0``. ``dev=None`` is
    bit-identical to the pre-DeviceModel kernel (no extra inputs, no extra
    ops).
    """
    S, M, N = planes.shape
    T = x.shape[0]
    assert x.shape == (T, M) and dh.shape == (T, N)
    assert noise is None or rkey is None, "pass a noise grid OR key words, not both"
    rng = None
    if noise is not None:
        rng = "grid"
    elif rkey is not None:
        rng = rng_impl
    bm, bn, bt = pick_block(M, bm), pick_block(N, bn), pick_block(T, bt)
    nk = T // bt
    grid = (M // bm, N // bn, nk)
    in_specs = [
        pl.BlockSpec((1, 2), lambda i, j, k: (0, 0), memory_space=pltpu.SMEM),
        pl.BlockSpec((bt, bm), lambda i, j, k: (k, i)),
        pl.BlockSpec((bt, bn), lambda i, j, k: (k, j)),
        pl.BlockSpec((S, bm, bn), lambda i, j, k: (0, i, j)),
    ]
    args = [
        jnp.stack([jnp.asarray(scale, jnp.float32),
                   jnp.asarray(grid_scale, jnp.float32)]).reshape(1, 2),
        x.astype(jnp.float32),
        dh.astype(jnp.float32),
        planes,
    ]
    if rng == "grid":
        in_specs.append(pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)))
        args.append(noise.astype(jnp.float32))
    elif rng is not None:
        in_specs.append(
            pl.BlockSpec((1, 2), lambda i, j, k: (0, 0), memory_space=pltpu.SMEM)
        )
        args.append(jnp.asarray(rkey, jnp.int32).reshape(1, 2))
    if dev is not None and dev.write_noise > 0.0:
        assert dkey is not None, "dev.write_noise > 0 requires write-noise key words"
        in_specs.append(
            pl.BlockSpec((1, 2), lambda i, j, k: (0, 0), memory_space=pltpu.SMEM)
        )
        args.append(jnp.asarray(dkey, jnp.int32).reshape(1, 2))
    return pl.pallas_call(
        functools.partial(_opa_fused_kernel, spec=spec, nk=nk, rng=rng, dev=dev),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((S, bm, bn), lambda i, j, k: (0, i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct(planes.shape, jnp.int8),
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="panther_opa_fused",
    )(*args)
