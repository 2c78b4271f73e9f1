"""Pure-jnp oracle for the sliced-MVM kernel.

Models the physical 128x128 crossbar tiling: the logical [M, N] matrix is cut
into 128-row tiles; each tile's analog column sum passes through its own ADC
(per slice, per input-bit cycle) before the digital shift-and-add combines
bits, slices, and row-tiles.

Three implementations:

``mvm_sliced_ref``    — the bit-plane packed schedule (mirrors the Pallas
                        kernel): the ``io_bits-1`` sign·magnitude planes of
                        ``x_q`` are extracted once, one einsum per row tile
                        contracts all (bit, slice) pairs at once, the ADC
                        applies elementwise on the ``[T, B, S, bn]`` block,
                        and the shift-and-add is a single contraction with
                        the static ``2^t·16^s`` grid.

``mvm_sliced_fused_ref`` — the quantize-fused entry: takes FLOAT activations
                        plus the DAC exponent and performs the
                        ``io_bits``-bit DAC quantize in the prologue (the
                        exact ``core.fixed_point.quantize`` arithmetic, so
                        the integer product grid is bit-identical to the
                        unfused composition). The finite-ADC schedule is
                        additionally restructured for locality: the digit
                        planes are prescaled by the inverse ADC step once,
                        the per-tile contraction keeps its natural
                        ``[T, B, S, bn]`` layout, the ADC reduces to a fused
                        round+clip producing integer codes, and the digital
                        shift-and-add becomes a leading-axis bit fold + a
                        per-slice fold with the step folded back into the
                        static weights — no 4-D transpose, no separate
                        divide pass. Same numbers up to f32 reassociation
                        (exact at ``adc_bits=None``, where the ideal branch
                        is kept verbatim for bit-identity).

``mvm_sliced_looped`` — the seed's serial per-(slice, bit) schedule, kept as
                        the bit-exactness oracle for property tests (one tiny
                        matmul per (tile, s, t), exactly the paper's cycle
                        ordering).

``transpose=True`` selects the MᵀVM (layer-gradient) read: the same crossbar
driven from the columns, contracting over 128-column tiles.

``device`` (a ``models.common.DeviceModel`` with ``read_noise > 0``) mirrors
the kernel's frozen per-(crossbar tile, slice, output column) ADC-channel
offsets bit-for-bit at the ideal-ADC branch (same counter-hash Gaussian at
the same global coordinates, same closed-form ``2^(io_bits-1)-1`` fold) and
analytically exactly at finite ADC (the restructured 1/step prescale turns
the current-unit offset into ``read_noise·2^(adc_bits-1)`` code units).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.fixed_point import exp2i
from repro.core.mvm import _adc, bit_planes, shift_add_scales
from repro.core.slicing import LOGICAL_BITS, SliceSpec
from repro.kernels.sliced_mvm.kernel import READ_SALT, READ_SALT_T

XBAR_ROWS = 128
# every contraction here runs in f32 (HIGHEST): on a TPU the default is one
# bf16 pass, which would round the int16 DAC codes and the 1/step-prescaled
# planes — the kernels' exact integer sums would then not be matched
HIGHEST = jax.lax.Precision.HIGHEST


def read_offsets_ref(device, spec: SliceSpec, gtile, col0, n_cols: int,
                     transpose: bool):
    """Frozen per-(tile, slice, column) read offsets in current units,
    ``[S, n_cols]`` at GLOBAL coordinates (crossbar tile ``gtile``, columns
    ``col0 + arange(n_cols)``) — the reference half of
    ``kernel.read_offsets`` (identical hash, identical float ops, different
    layout: per-slice rows instead of slice-stacked columns)."""
    from repro.core.fixed_point import counter_gauss, device_pattern_words

    S = spec.n_slices
    w0, w1 = device_pattern_words(
        device.stuck_seed, READ_SALT_T if transpose else READ_SALT
    )
    c = jnp.asarray(col0, jnp.int32) + jax.lax.broadcasted_iota(
        jnp.int32, (1, n_cols), 1
    )
    rows = []
    for s in range(S):
        r = (jnp.asarray(gtile, jnp.int32) * S + s).reshape(1, 1)
        g = counter_gauss(r, c, jnp.int32(w0), jnp.int32(w1))
        fs = float(XBAR_ROWS * spec.plane_max[s])
        rows.append(g * jnp.float32(device.read_noise * fs))
    return jnp.concatenate(rows, axis=0)  # [S, n_cols]


def dac_quantize(x, frac_bits, io_bits: int):
    """The DAC prologue: float -> ``io_bits`` fixed point on the ``2^-F``
    grid — the exact arithmetic of ``core.fixed_point.quantize`` (round,
    saturate), inlined so fused entries produce bit-identical integers."""
    lim = float(2 ** (io_bits - 1) - 1)
    y = jnp.round(x.astype(jnp.float32) * exp2i(frac_bits))
    return jnp.clip(y, -lim, lim).astype(jnp.int32)


def mvm_sliced_ref(
    planes,
    x_q,
    spec: SliceSpec,
    io_bits: int = 16,
    adc_bits: int | None = None,
    xbar_rows: int = XBAR_ROWS,
    transpose: bool = False,
):
    """planes int8 [S,M,N]; x_q int [B,M] ([B,N] when ``transpose``) -> f32
    [B,N] ([B,M]) on the product grid."""
    w = planes.astype(jnp.float32)
    if transpose:
        w = jnp.swapaxes(w, 1, 2)
    S, M, N = w.shape
    B = x_q.shape[0]
    assert x_q.shape == (B, M)
    n_tiles = -(-M // xbar_rows)
    full_scale = xbar_rows * jnp.asarray(spec.plane_max, jnp.float32)  # [S]
    out = jnp.zeros((B, N), jnp.float32)

    if adc_bits is None:
        # Ideal ADC: bit-streaming is exact — contract the full input per
        # slice and fold 16^s (row tiling is then irrelevant to the value,
        # but kept so the accumulation order matches the finite-ADC path).
        xf = x_q.astype(jnp.float32)
        s_scale = jnp.exp2(LOGICAL_BITS * jnp.arange(S, dtype=jnp.float32))
        for tile in range(n_tiles):
            lo, hi = tile * xbar_rows, min((tile + 1) * xbar_rows, M)
            y = jnp.einsum("bm,smn->bsn", xf[:, lo:hi], w[:, lo:hi],
                           precision=HIGHEST, preferred_element_type=jnp.float32)
            out = out + jnp.einsum("bsn,s->bn", y, s_scale, precision=HIGHEST)
        return out

    bp = bit_planes(x_q, io_bits).astype(jnp.float32)  # [T, B, M], extracted once
    scales = shift_add_scales(spec, io_bits)  # [T, S]
    for tile in range(n_tiles):
        lo, hi = tile * xbar_rows, min((tile + 1) * xbar_rows, M)
        y = jnp.einsum("tbm,smn->tbsn", bp[:, :, lo:hi], w[:, lo:hi],
                       precision=HIGHEST, preferred_element_type=jnp.float32)
        y = _adc(y, full_scale[:, None], adc_bits)
        out = out + jnp.einsum("tbsn,ts->bn", y, scales, precision=HIGHEST)
    return out


def mvm_sliced_fused_ref(
    planes,
    x,
    frac_bits,
    spec: SliceSpec,
    io_bits: int = 16,
    adc_bits: int | None = None,
    xbar_rows: int = XBAR_ROWS,
    transpose: bool = False,
    device=None,
    tile0=0,
    col0=0,
):
    """Quantize-fused packed MVM: planes int8 [S,M,N]; x FLOAT [B,M] ([B,N]
    when ``transpose``); frac_bits int32 scalar DAC exponent -> f32 [B,N]
    ([B,M]) on the product grid (caller applies ``2^-(xf+F)``).

    The DAC quantize happens here — callers never materialise the int32
    operand or its bit planes. At ``adc_bits=None`` the value is
    bit-identical to ``mvm_sliced_ref(planes, dac_quantize(x, ...))``; at
    finite ADC the restructured fold reassociates f32 sums (same analog
    model, values within the kernel-vs-ref tolerance). ``device`` with
    ``read_noise > 0`` injects the frozen ADC-channel offsets (module
    docstring); ``tile0``/``col0`` are the global tile/column offsets of a
    shard (int32, default 0).
    """
    w = planes.astype(jnp.float32)
    if transpose:
        w = jnp.swapaxes(w, 1, 2)
    S, M, N = w.shape
    B = x.shape[0]
    assert x.shape == (B, M)
    x_q = dac_quantize(x, frac_bits, io_bits)
    n_tiles = -(-M // xbar_rows)
    out = jnp.zeros((B, N), jnp.float32)
    noisy = device is not None and device.read_noise > 0.0

    def offs(tile):
        return read_offsets_ref(
            device, spec, jnp.asarray(tile0, jnp.int32) + tile, col0, N, transpose
        )

    if adc_bits is None:
        # Kept verbatim from mvm_sliced_ref's ideal branch: fused and
        # unfused entries are bit-identical here (property-tested). The
        # noisy add mirrors the kernel's closed form exactly: each of the
        # io_bits-1 bit cycles reads the same frozen channel offset.
        xf = x_q.astype(jnp.float32)
        s_scale = jnp.exp2(LOGICAL_BITS * jnp.arange(S, dtype=jnp.float32))
        for tile in range(n_tiles):
            lo, hi = tile * xbar_rows, min((tile + 1) * xbar_rows, M)
            y = jnp.einsum("bm,smn->bsn", xf[:, lo:hi], w[:, lo:hi],
                           precision=HIGHEST, preferred_element_type=jnp.float32)
            if noisy:
                y = y + offs(tile)[None] * float(2 ** (io_bits - 1) - 1)
            out = out + jnp.einsum("bsn,s->bn", y, s_scale, precision=HIGHEST)
        return out

    T = io_bits - 1
    bp = bit_planes(x_q, io_bits).astype(jnp.float32)  # [T, B, M]
    full_scale = xbar_rows * jnp.asarray(spec.plane_max, jnp.float32)  # [S]
    step = 2.0 * full_scale / float(2**adc_bits)
    half = float(2 ** (adc_bits - 1))
    # Prescale the planes by 1/step so the ADC is a bare round+clip to
    # integer codes; step folds back into the per-slice shift-add weights.
    w2 = w * (1.0 / step)[:, None, None]
    tw = jnp.exp2(jnp.arange(T, dtype=jnp.float32))
    sw = step * jnp.exp2(LOGICAL_BITS * jnp.arange(S, dtype=jnp.float32))
    inv_step = (1.0 / step)[:, None]  # current units -> ADC code units
    for tile in range(n_tiles):
        lo, hi = tile * xbar_rows, min((tile + 1) * xbar_rows, M)
        y = jnp.einsum("tbm,smn->tbsn", bp[:, :, lo:hi], w2[:, lo:hi],
                       precision=HIGHEST, preferred_element_type=jnp.float32)
        if noisy:
            # channel offset on the raw current, pre-round (prescaled grid)
            y = y + (offs(tile) * inv_step)[None, None]
        q = jnp.clip(jnp.round(y), -half, half)  # integer ADC codes
        z = jnp.tensordot(tw, q, axes=([0], [0]), precision=HIGHEST)  # bit fold -> [B, S, n]
        out = out + jnp.einsum("bsn,s->bn", z, sw, precision=HIGHEST)  # slice fold (step folded)
    return out


def mvm_sliced_looped(
    planes,
    x_q,
    spec: SliceSpec,
    io_bits: int = 16,
    adc_bits: int | None = None,
    xbar_rows: int = XBAR_ROWS,
    transpose: bool = False,
):
    """Seed schedule: one serial matmul per (tile, slice, bit) — the
    bit-exactness oracle the packed forms are property-tested against."""
    w_all = planes.astype(jnp.int32)
    if transpose:
        w_all = jnp.swapaxes(w_all, 1, 2)
    S, M, N = w_all.shape
    B = x_q.shape[0]
    assert x_q.shape == (B, M)
    n_tiles = -(-M // xbar_rows)
    sx = jnp.sign(x_q).astype(jnp.int32)
    mx = jnp.abs(x_q).astype(jnp.int32)
    out = jnp.zeros((B, N), jnp.float32)
    for tile in range(n_tiles):
        lo, hi = tile * xbar_rows, min((tile + 1) * xbar_rows, M)
        for s in range(S):
            w = w_all[s, lo:hi]
            full_scale = float(xbar_rows * spec.plane_max[s])
            for t in range(io_bits - 1):
                bt = ((mx[:, lo:hi] >> t) & 1) * sx[:, lo:hi]
                col = bt @ w  # [B, N] analog column current of this tile
                col = _adc(col, full_scale, adc_bits)
                out = out + col * float(2 ** t * 2 ** (LOGICAL_BITS * s))
    return out
