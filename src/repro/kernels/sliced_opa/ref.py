"""Pure-jnp oracle for the sliced-OPA kernels (delegates to repro.core).

Device non-idealities (``device``, a ``models.common.DeviceModel``) mirror
the kernel finalize bit-for-bit, in the same physical order: update
asymmetry on the signed analog increment, counter-hash Gaussian write noise
(independent key stream, ``fold_in(key, WRITE_NOISE_FOLD)``), grid rounding,
digit deposit, then the static stuck-cell mask (stuck cells keep their
pre-update digit). ``device=None`` is the verbatim pre-DeviceModel oracle.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import SliceSpec, opa_batched, product_digits, saturating_add
from repro.core.fixed_point import (
    WRITE_NOISE_FOLD,
    counter_gauss_array,
    counter_u01,
    device_pattern_words,
    exp2i,
    quantize,
    rounding_noise,
)


def opa_deposit_ref(planes, p_q, spec: SliceSpec):
    """planes int8 [S,M,N], p_q int32 [M,N] -> int8 [S,M,N]."""
    return opa_batched(planes, p_q, spec)


def stuck_mask_ref(device, spec: SliceSpec, shape):
    """The kernel's static per-slice stuck-cell mask at global coordinates,
    for planes of ``shape`` [S, *stack, M, N]. The (row, col) pattern is a
    pure function of ``(stuck_seed, slice)`` and broadcasts over lax.scan
    layer-stack dims, exactly as one traced kernel launch serves every
    stacked layer."""
    S, (M, N) = shape[0], shape[-2:]
    r = jax.lax.broadcasted_iota(jnp.int32, (M, N), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (M, N), 1)
    frac = jnp.float32(device.stuck_frac)
    masks = []
    for s in range(S):
        w0, w1 = device_pattern_words(device.stuck_seed, s)
        masks.append(counter_u01(r, c, jnp.int32(w0), jnp.int32(w1)) < frac)
    mask = jnp.stack(masks, axis=0)  # [S, M, N]
    return mask.reshape((S,) + (1,) * (len(shape) - 3) + (M, N))


def write_device(y, device, *, key, stochastic, rng_mode):
    """Asymmetry + write noise on the grid-scaled analog increment ``y``,
    then the rounding the ideal path would apply — the ref half of the
    kernel finalize (shapes [*stack, M, N])."""
    if device.asym_up != 1.0 or device.asym_down != 1.0:
        y = jnp.where(
            y >= 0.0,
            y * jnp.float32(device.asym_up),
            y * jnp.float32(device.asym_down),
        )
    if device.write_noise > 0.0:
        if key is None:
            raise ValueError("DeviceModel.write_noise requires a PRNG key")
        dk = jax.random.fold_in(key, WRITE_NOISE_FOLD)
        y = y + jnp.float32(device.write_noise) * counter_gauss_array(dk, y.shape)
    if stochastic:
        y = jnp.floor(y + rounding_noise(key, y.shape, rng_mode))
    else:
        y = jnp.round(y)
    lim = float(2**31 - 1)
    return jnp.clip(y, -lim, lim).astype(jnp.int32)


def opa_fused_update_ref(planes, x, dh, lr, frac_bits, spec: SliceSpec, *,
                         stochastic: bool = False, key=None,
                         rng_mode: str = "counter", device=None):
    """Operand-form OPA update oracle: exact mirror of the dense pipeline.

    ``einsum(x, dh)`` in the operand dtype is the same contraction XLA's AD
    emits for ``x @ w`` on the dense-grad path (at the kernel's HIGHEST
    precision, so f32 operands are not rounded to one bf16 pass on a TPU), and ``quantize`` is the same
    call ``optim.panther`` makes there — so this oracle (and the CPU
    dispatch of ``opa_fused_update``) is bit-identical to dense-grad +
    ``opa_deposit``, including the stochastic-rounding draw for a given
    (key, rng_mode). With ``rng_mode="counter"`` the draw is additionally
    bit-identical to the Pallas kernel's in-kernel generation. ``device``
    (already normalized: None unless some write-path field is non-ideal)
    reroutes through the device-physics mirror of the kernel finalize.
    """
    g = jnp.einsum("...tm,...tn->...mn", x, dh, precision=jax.lax.Precision.HIGHEST)
    if device is None:
        upd = quantize(-lr * g.astype(jnp.float32), frac_bits,
                       stochastic=stochastic, key=key, rng_mode=rng_mode)
        return opa_batched(planes, upd, spec)
    # (g * -lr) * 2^F, as the kernel and quantize() round it
    y = g.astype(jnp.float32) * -jnp.asarray(lr, jnp.float32) * exp2i(frac_bits)
    upd = write_device(y, device,
                       key=key, stochastic=stochastic, rng_mode=rng_mode)
    new = opa_batched(planes, upd, spec)
    if device.stuck_frac > 0.0:
        new = jnp.where(stuck_mask_ref(device, spec, planes.shape), planes, new)
    return new


def opa_fused_ref(planes, x, dh, scale, spec: SliceSpec, *, device=None,
                  dkey=None):
    """Fused grad-outer-product + quantize + deposit oracle.

    planes int8 [S,M,N]; x f32 [T,M] layer inputs; dh f32 [T,N] scaled output
    errors (-lr already folded); scale f32 scalar = 2**F weight grid.
    ``device``/``dkey`` mirror the kernel's raw entry (``dkey`` int32 [2]
    write-noise key words, matching the kernel's SMEM prefetch).
    """
    acc = jnp.einsum("tm,tn->mn", x.astype(jnp.float32), dh.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    lim = float(2**31 - 1)
    y = acc * jnp.asarray(scale, jnp.float32)
    if device is not None:
        if device.asym_up != 1.0 or device.asym_down != 1.0:
            y = jnp.where(
                y >= 0.0,
                y * jnp.float32(device.asym_up),
                y * jnp.float32(device.asym_down),
            )
        if device.write_noise > 0.0:
            from repro.core.fixed_point import counter_gauss

            assert dkey is not None, "dev.write_noise > 0 requires key words"
            M, N = acc.shape
            r = jax.lax.broadcasted_iota(jnp.int32, (M, N), 0)
            c = jax.lax.broadcasted_iota(jnp.int32, (M, N), 1)
            y = y + jnp.float32(device.write_noise) * counter_gauss(
                r, c, dkey[0], dkey[1]
            )
    p_q = jnp.clip(jnp.round(y), -lim, lim).astype(jnp.int32)
    new = saturating_add(planes, product_digits(p_q, spec), spec)
    if device is not None and device.stuck_frac > 0.0:
        new = jnp.where(stuck_mask_ref(device, spec, planes.shape), planes, new)
    return new
