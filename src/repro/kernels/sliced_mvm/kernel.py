"""Pallas TPU kernel for bit-exact sliced MVM with a finite-ADC model.

The logical [M, N] weight is blocked into (xbar_rows=128)-row tiles — the
physical crossbar height — so the ADC quantization boundary in the kernel is
exactly the hardware's. Grid = (B/bb, N/bn, M/128) with the row-tile dim
innermost ("arbitrary"): the f32 accumulator lives in VMEM scratch across
contraction tiles and is written out once.

Token blocks: ``bb`` is chosen from the flattened token count
(``pick_token_block``): the largest divisor of ``B`` on the 8-row granule,
up to ``BB_CAP`` = 128 rows and the VMEM limit. Each plane tile is DMA'd,
converted and loaded into the MXU once per token block, so a 512-token read
does that 4 times per tile where 8-row blocks did it 64 times; small token
counts (decode, expert reads) read in one block of the whole batch. Each
output row depends only on its own input row: any ``bb`` gives the same bits.

Packed schedule (per crossbar tile, see ``_tile_compute``):

1. **Bit-plane packing** — the ``io_bits-1`` sign·magnitude planes of the
   int input block are extracted once and stacked into a single
   ``[(io_bits-1)·bb, 128]`` MXU operand (the seed kernel re-derived each
   plane per slice and issued a ``[bb, 128]`` matmul per (slice, bit):
   ``S·(io_bits-1)`` = 120 dots at ~6% MXU row utilization).
2. **Slice-stacked weights** — the S digit planes, converted once per tile
   and token block, concatenate along columns into ``[128, S·bn]``, so ONE
   ``dot_general`` computes every (bit, slice) analog column current of the
   tile.
3. **ADC in the code domain** (``_adc_fold``) — every ADC step
   ``2·128·plane_max[s] / 2^adc_bits`` is a power of two, so the digits are
   scaled by ``1/step_s`` as the tile is converted (exact in bf16) and the
   MXU yields the ``[(io_bits-1)·bb, S·bn]`` currents in step units; the ADC
   is then a round and a clip to ``±2^(adc_bits-1)``: integer codes, no
   division and no rescale on the grid.
4. **Digital shift-and-add** — the static ``2^t`` weights fold the codes over
   the row blocks and ``16^s·step_s`` over the column blocks (cheap VPU
   adds), then the tile lands in the f32 accumulator. Scaling by a power of
   two commutes with every rounded add, so this is bit-identical to
   ``core.mvm._adc`` followed by the ``2^t`` and ``16^s`` folds (tested).

``adc_bits=None`` takes an in-kernel ideal-ADC branch: bit-streaming is
exact under an ideal ADC, so the kernel contracts ``x_q`` against the
slice-stacked planes directly (one dot, no bit dimension) — provably equal
to the streamed form, asserted at the ops level and in tests.

``transpose=True`` is the MᵀVM (layer-gradient) read: the same crossbar
driven from the columns. The contraction runs over 128-column tiles of the
logical matrix with the identical packed schedule (the ADC full scale stays
``128·plane_max`` — square crossbars).

**Quantize-fused entry** (``mvm_sliced_fused``): the DAC boundary lives
inside the kernel. The float activation block is the only operand that
crosses HBM; the tile prologue (``_dac_block``) performs the ``io_bits``
round/saturate onto the ``2^-frac_bits`` grid — the exact arithmetic of
``core.fixed_point.quantize``, with the scale built by the same ``exp2i``
bitcast (outside the kernel) so fused and unfused integer grids are
bit-identical — and the bit-plane extraction happens per tile in VMEM. The
scale ``2^frac_bits`` enters as an f32 scalar through SMEM. No
``x_q``-shaped or ``[T, B, M]`` plane array exists at the pallas_call
boundary (jaxpr-audited by
``kernels.common.forbid_pallas_inputs`` in tests and the bench gate).

**Double-buffered tile DMA** (``double_buffer=True``, the default fused
lowering): the grid drops to 2-D (batch, out) and the crossbar-tile loop
runs inside the kernel — digit planes stay in HBM/ANY and each 128-row tile
block is DMA'd into one of two VMEM slots while the MXU contracts the other
(start slot ``k+1`` before waiting on slot ``k``; one DMA semaphore per
slot). ``double_buffer=False`` keeps the 3-D grid lowering for equivalence
tests; both compute identical numbers (same per-tile body, same k order).

This kernel is the fidelity path (and the Fig-9/10 engine); production
training uses the lossless dequantize->MXU fast path, which equals this
kernel at adc_bits=None (asserted in tests).

Non-ideal device read noise (``dev``, a ``models.common.DeviceModel`` with
``read_noise > 0``): the read-path non-ideality enters between the analog
column current and the ADC — a **static** per-(crossbar tile, slice, output
column) Gaussian offset with sigma ``read_noise`` relative to that slice's
ADC full scale, modeling a per-sense-amp/ADC-channel offset (the forward
read sits inside a custom-vjp primal with no RNG threading, so the pattern
is frozen, keyed by ``stuck_seed`` like the stuck-cell mask; transpose reads
salt the hash — a different ADC bank serves the MᵀVM direction). At finite
ADC the offset adds to the raw currents before ``_adc``; the ideal-ADC
branch folds the closed form — each of the ``io_bits-1`` bit cycles reads
the same channel offset, so the streamed sum picks it up with weight
``2^(io_bits-1) - 1``. Global (tile, column) coordinates come in through an
SMEM offset pair so sharded lowerings reproduce the single-host pattern.
``dev=None`` compiles the exact pre-DeviceModel kernel.
"""
from __future__ import annotations

import collections
import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.fixed_point import exp2i
from repro.core.mvm import _adc
from repro.core.slicing import LOGICAL_BITS, SliceSpec
from repro.kernels.common import pick_block

XBAR_ROWS = 128
TOKEN_GRANULE = 8  # sublane granule of the token (batch) block
DEFAULT_BN = 256
# Token blocks: one plane tile is DMA'd, converted and loaded into the MXU
# once per token block, so the block is as tall as the flattened token count
# allows, up to BB_CAP rows and the VMEM limit for the block's buffers.
BB_CAP = 128
VMEM_LIMIT = 32 * 2**20  # scoped-VMEM limit the read kernels compile under


def read_vmem_bytes(bb: int, bn: int, contract: int, spec: SliceSpec,
                    io_bits: int, adc_bits: int | None) -> int:
    """VMEM one read program holds at token block ``bb``: the f32 product
    grid ``[(io_bits-1)·bb, S·bn]`` (``[bb, S·bn]`` at the ideal ADC), the f32
    activation strip ``[bb, contract]`` and the output block, both
    double-buffered, the two int8 plane slots and the tile's converted
    MXU operand."""
    S = spec.n_slices
    rows = bb if adc_bits is None else (io_bits - 1) * bb
    grid = rows * S * bn * 4
    strip = 2 * bb * contract * 4
    out = 2 * bb * bn * 4
    planes = 2 * S * XBAR_ROWS * bn
    w_cat = S * XBAR_ROWS * bn * (4 if adc_bits is None else 2)
    return grid + strip + out + planes + w_cat


def pick_token_block(B: int, bn: int, contract: int, spec: SliceSpec,
                     io_bits: int, adc_bits: int | None) -> int:
    """Tallest token block for a read of ``B`` tokens: the largest divisor of
    ``B`` on the 8-row granule up to ``BB_CAP`` whose buffers fit
    ``VMEM_LIMIT``. Up to the cap that is ``B`` itself; counts with no such
    divisor keep ``pick_block``'s fallback."""
    best = pick_block(B, TOKEN_GRANULE, granule=TOKEN_GRANULE)
    for cand in range(TOKEN_GRANULE, min(B, BB_CAP) + 1, TOKEN_GRANULE):
        if B % cand == 0 and read_vmem_bytes(
                cand, bn, contract, spec, io_bits, adc_bits) <= VMEM_LIMIT:
            best = cand
    return best


def _dac_block(x, scale, io_bits: int):
    """In-kernel DAC prologue: float block -> int32 on the ``2^-frac_bits``
    grid, saturated to ``io_bits`` signed — the exact arithmetic of
    ``core.fixed_point.quantize``. ``scale`` is the f32 ``exp2i(frac_bits)``
    computed outside the kernel and read from SMEM: the identical power of
    two as the unfused path (Mosaic cannot bitcast an SMEM scalar)."""
    lim = float(2 ** (io_bits - 1) - 1)
    y = jnp.round(x.astype(jnp.float32) * scale)
    return jnp.clip(y, -lim, lim).astype(jnp.int32)


# salts separating the frozen read-offset pattern streams (MVM vs MᵀVM ADC
# banks) from the stuck-cell mask stream (salt = slice index, small ints)
READ_SALT = 0x52D
READ_SALT_T = 0x52E


def read_offsets(dev, spec: SliceSpec, tile_idx, col0, bn: int, transpose: bool):
    """Static per-(tile, slice, column) read-current offsets, already scaled
    to current units: ``read_noise * full_scale_s * N(0,1)`` laid out
    ``[1, S*bn]`` along the slice-stacked column blocks. Pure function of the
    GLOBAL coordinates (``tile_idx`` crossbar-tile index, ``col0`` column
    offset of this block) and ``(stuck_seed, transpose)`` — identical for
    any blocking, any sharding, kernel or reference."""
    from repro.core.fixed_point import counter_gauss, device_pattern_words

    S = spec.n_slices
    w0, w1 = device_pattern_words(dev.stuck_seed, READ_SALT_T if transpose else READ_SALT)
    c = jnp.asarray(col0, jnp.int32) + jax.lax.broadcasted_iota(jnp.int32, (1, bn), 1)
    outs = []
    for s in range(S):
        r = (jnp.asarray(tile_idx, jnp.int32) * S + s).reshape(1, 1)
        g = counter_gauss(r, c, jnp.int32(w0), jnp.int32(w1))
        fs = float(XBAR_ROWS * spec.plane_max[s])
        outs.append(g * jnp.float32(dev.read_noise * fs))
    return jnp.concatenate(outs, axis=1)  # [1, S*bn]


def adc_steps(spec: SliceSpec, adc_bits: int) -> tuple:
    """Per-slice ADC step ``2·128·plane_max[s] / 2^adc_bits`` (the
    ``core.mvm._adc`` quantizer over the ``±128·plane_max[s]`` full scale).
    ``plane_max[s] = 2^(bits[s]-1)``, so every step is a power of two: scaling
    by one commutes with every rounded f32 add and multiply, which is what
    lets the kernel run the ADC on integer codes (asserted here, at trace
    time)."""
    steps = tuple(2.0 * XBAR_ROWS * pm / 2**adc_bits for pm in spec.plane_max)
    assert all(math.frexp(st)[0] == 0.5 for st in steps), steps
    return steps


def _adc_fold(y, *, spec: SliceSpec, io_bits: int, adc_bits: int, bb: int, bn: int):
    """Finite-ADC epilogue of one crossbar tile in the code domain: the
    ``[(io_bits-1)·bb, S·bn]`` column currents in units of their slice's ADC
    step (bit blocks down, slice blocks across) -> f32 ``[bb, bn]``.

    The ADC is a round and a clip to the integer codes ``±2^(adc_bits-1)``:
    no division, no rescale. The ``2^t`` bit fold sums the codes and
    ``step_s`` rides in the ``16^s`` slice-fold constants. Every step being a
    power of two (``adc_steps``), each partial sum is ``step_s`` times the
    current-domain form's (``core.mvm._adc``, then the folds): the same bits."""
    S = spec.n_slices
    steps = adc_steps(spec, adc_bits)
    lim = float(2 ** (adc_bits - 1))
    c = jnp.clip(jnp.round(y), -lim, lim)
    # shift-and-add, bit half: fold 2^t over the stacked row blocks
    z = c[0:bb]
    for t in range(1, io_bits - 1):
        z = z + c[t * bb:(t + 1) * bb] * float(2**t)
    # slice half: fold 16^s · step_s over the stacked column blocks
    acc = z[:, 0:bn] * steps[0]
    for s in range(1, S):
        acc = acc + z[:, s * bn:(s + 1) * bn] * float(2 ** (LOGICAL_BITS * s) * steps[s])
    return acc


def _tile_compute(xq, w, *, spec: SliceSpec, io_bits: int, adc_bits: int | None,
                  transpose: bool = False, dev=None, tile_idx=None, col0=None):
    """Product-grid contribution of one crossbar tile (pure array -> array;
    shared by the Pallas kernel body and the jaxpr primitive checks).

    xq int32 [bb, 128] input block; w int8 [S, 128, bn] digit-plane block
    ([S, bn, 128] when ``transpose``). Returns f32 [bb, bn]. ``dev`` with
    ``read_noise > 0`` adds the frozen per-ADC-channel offsets (module
    docstring) at global coordinates ``(tile_idx, col0)``.
    """
    S = spec.n_slices
    if transpose:
        dims, axis, bn = (((1,), (1,)), ((), ())), 0, w.shape[1]  # [*, 128] x [S*bn, 128]
    else:
        dims, axis, bn = (((1,), (0,)), ((), ())), 1, w.shape[2]  # [*, 128] x [128, S*bn]
    noisy = dev is not None and dev.read_noise > 0.0

    if adc_bits is None:
        # ideal ADC: bit-streaming is exact -> contract the full input once.
        # xq carries up to io_bits-1 = 15 magnitude bits, more than one bf16
        # pass holds: HIGHEST (f32 contraction) keeps every product exact.
        w_cat = jnp.concatenate([w[s].astype(jnp.float32) for s in range(S)], axis=axis)
        z = jax.lax.dot_general(
            xq.astype(jnp.float32), w_cat, dims,
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )  # [bb, S*bn]
        if noisy:
            # each of the io_bits-1 bit cycles reads the same frozen channel
            # offset: the streamed shift-and-add folds it with sum(2^t)
            offs = read_offsets(dev, spec, tile_idx, col0, bn, transpose)
            z = z + offs * float(2 ** (io_bits - 1) - 1)
        # shift-and-add, slice half: fold 16^s over the stacked column blocks
        acc = z[:, 0:bn]
        for s in range(1, S):
            acc = acc + z[:, s * bn:(s + 1) * bn] * float(2 ** (LOGICAL_BITS * s))
        return acc

    # the tile's digits in ADC step units, once per tile: |d| <= 128 times a
    # power of two stays exact in bf16, so the MXU sums the currents already
    # divided by the step (exactly: every column sum is a multiple of 1/step
    # under 128 * 128 / step)
    inv_step = [1.0 / st for st in adc_steps(spec, adc_bits)]
    w_cat = jnp.concatenate(
        [(w[s].astype(jnp.float32) * inv_step[s]).astype(jnp.bfloat16) for s in range(S)],
        axis=axis,
    )
    bb = xq.shape[0]
    sx = jnp.sign(xq)
    mx = jnp.abs(xq)
    # bit-plane packed operand, extracted once per tile: [(io_bits-1)*bb, 128]
    xp = jnp.concatenate(
        [((mx >> t) & 1) * sx for t in range(io_bits - 1)], axis=0
    ).astype(jnp.float32)
    # one bf16 MXU pass computes every (bit, slice) column current at once
    y = jax.lax.dot_general(
        xp.astype(jnp.bfloat16), w_cat, dims,
        preferred_element_type=jnp.float32,
    )  # [(io_bits-1)*bb, S*bn]
    if noisy:
        # per-ADC-channel offset on the raw column current, pre-ADC
        inv_row = jnp.concatenate(
            [jnp.full((1, bn), v, jnp.float32) for v in inv_step], axis=1)
        y = y + read_offsets(dev, spec, tile_idx, col0, bn, transpose) * inv_row
    return _adc_fold(y, spec=spec, io_bits=io_bits, adc_bits=adc_bits, bb=bb, bn=bn)


def tile_primitives(spec: SliceSpec, io_bits: int = 16, adc_bits: int | None = None,
                    transpose: bool = False, bb: int = TOKEN_GRANULE,
                    bn: int = DEFAULT_BN) -> collections.Counter:
    """Primitive counts of the exact tile body the kernel runs (its jaxpr)."""
    wshape = (spec.n_slices, bn, XBAR_ROWS) if transpose else (spec.n_slices, XBAR_ROWS, bn)
    fn = functools.partial(
        _tile_compute, spec=spec, io_bits=io_bits, adc_bits=adc_bits, transpose=transpose
    )
    jaxpr = jax.make_jaxpr(fn)(
        jnp.zeros((bb, XBAR_ROWS), jnp.int32), jnp.zeros(wshape, jnp.int8)
    )
    return collections.Counter(eqn.primitive.name for eqn in jaxpr.jaxpr.eqns)


def tile_dot_count(spec: SliceSpec, io_bits: int = 16, adc_bits: int | None = None,
                   transpose: bool = False, bb: int = TOKEN_GRANULE,
                   bn: int = DEFAULT_BN) -> int:
    """Number of MXU ``dot_general`` ops the kernel issues per crossbar tile.
    The packed schedule is 1; the seed schedule was ``S * (io_bits - 1)``."""
    return tile_primitives(spec, io_bits, adc_bits, transpose, bb, bn)["dot_general"]


def _mvm_kernel(x_ref, planes_ref, out_ref, acc_ref, *, spec, io_bits, adc_bits, nk,
                transpose):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _tile_compute(
        x_ref[...].astype(jnp.int32), planes_ref[...],
        spec=spec, io_bits=io_bits, adc_bits=adc_bits, transpose=transpose,
    )

    @pl.when(k == nk - 1)
    def _finalize():
        out_ref[...] = acc_ref[...]


@functools.partial(
    jax.jit,
    static_argnames=("spec", "io_bits", "adc_bits", "bb", "bn", "interpret", "transpose"),
)
def mvm_sliced(
    planes: jax.Array,
    x_q: jax.Array,
    *,
    spec: SliceSpec,
    io_bits: int = 16,
    adc_bits: int | None = None,
    bb: int | None = None,
    bn: int = DEFAULT_BN,
    interpret: bool = False,
    transpose: bool = False,
) -> jax.Array:
    """planes int8 [S,M,N]; x_q int32 [B,M] -> f32 [B,N] (product-grid).
    With ``transpose``: x_q int32 [B,N] -> f32 [B,M] (the MᵀVM read).
    ``bb=None`` takes the token block ``pick_token_block`` chooses."""
    S, M, N = planes.shape
    B = x_q.shape[0]
    contract, out_dim = (N, M) if transpose else (M, N)
    assert x_q.shape == (B, contract)
    assert contract % XBAR_ROWS == 0, (
        f"contraction dim {contract} must be a multiple of crossbar rows ({XBAR_ROWS})"
    )
    bn = pick_block(out_dim, bn)
    bb = (pick_token_block(B, bn, contract, spec, io_bits, adc_bits) if bb is None
          else pick_block(B, bb, granule=TOKEN_GRANULE))
    nk = contract // XBAR_ROWS
    grid = (B // bb, out_dim // bn, nk)
    if transpose:
        plane_spec = pl.BlockSpec((S, bn, XBAR_ROWS), lambda i, j, k: (0, j, k))
    else:
        plane_spec = pl.BlockSpec((S, XBAR_ROWS, bn), lambda i, j, k: (0, k, j))
    return pl.pallas_call(
        functools.partial(
            _mvm_kernel, spec=spec, io_bits=io_bits, adc_bits=adc_bits, nk=nk,
            transpose=transpose,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb, XBAR_ROWS), lambda i, j, k: (i, k)),
            plane_spec,
        ],
        out_specs=pl.BlockSpec((bb, bn), lambda i, j, k: (i, j)),
        scratch_shapes=[pltpu.VMEM((bb, bn), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((B, out_dim), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT,
        ),
        interpret=interpret,
        name="panther_mvm_sliced_t" if transpose else "panther_mvm_sliced",
    )(x_q, planes)


def _mvm_fused_kernel(scale_ref, x_ref, planes_ref, *rest, spec,
                      io_bits, adc_bits, nk, transpose, dev=None):
    rest = list(rest)
    off_ref = None
    if dev is not None and dev.read_noise > 0.0:
        off_ref = rest.pop(0)
    out_ref, acc_ref = rest
    j = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # DAC quantize fused into the tile prologue: the float activation block
    # is the only operand that crossed HBM.
    xq = _dac_block(x_ref[...], scale_ref[0, 0], io_bits)
    acc_ref[...] += _tile_compute(
        xq, planes_ref[...],
        spec=spec, io_bits=io_bits, adc_bits=adc_bits, transpose=transpose,
        dev=dev,
        tile_idx=None if off_ref is None else off_ref[0, 0] + k,
        col0=None if off_ref is None else off_ref[0, 1] + j * acc_ref.shape[1],
    )

    @pl.when(k == nk - 1)
    def _finalize():
        out_ref[...] = acc_ref[...]


def _mvm_fused_db_kernel(scale_ref, x_ref, planes_ref, *rest,
                         spec, io_bits, adc_bits, nk, bn, transpose, dev=None):
    """Double-buffered lowering: 2-D grid (batch, out) — the crossbar-tile
    loop runs *inside* the kernel over the full input strip, with the next
    tile's digit planes DMA'd from HBM/ANY into the spare VMEM slot while the
    MXU contracts the current one."""
    rest = list(rest)
    off_ref = None
    if dev is not None and dev.read_noise > 0.0:
        off_ref = rest.pop(0)
    out_ref, wtile_ref, sem = rest
    j = pl.program_id(1)  # program ids must be read at kernel top level
    scale = scale_ref[0, 0]

    def tile_copy(slot, kk):
        # identical descriptor for start and wait (same src/dst/sem triplet)
        if transpose:
            src = planes_ref.at[:, pl.ds(j * bn, bn), pl.ds(kk * XBAR_ROWS, XBAR_ROWS)]
        else:
            src = planes_ref.at[:, pl.ds(kk * XBAR_ROWS, XBAR_ROWS), pl.ds(j * bn, bn)]
        return pltpu.make_async_copy(src, wtile_ref.at[slot], sem.at[slot])

    tile_copy(0, 0).start()

    def body(k, acc):
        slot = jax.lax.rem(k, 2)

        @pl.when(k + 1 < nk)
        def _prefetch():
            tile_copy(jax.lax.rem(k + 1, 2), k + 1).start()

        tile_copy(slot, k).wait()
        # this tile's strip columns, read through the ref (Mosaic has no
        # dynamic_slice of a value) and DAC-quantized in the prologue
        col = pl.multiple_of(k * XBAR_ROWS, XBAR_ROWS)
        xq_k = _dac_block(x_ref[:, pl.ds(col, XBAR_ROWS)], scale, io_bits)
        return acc + _tile_compute(
            xq_k, wtile_ref[slot],
            spec=spec, io_bits=io_bits, adc_bits=adc_bits, transpose=transpose,
            dev=dev,
            tile_idx=None if off_ref is None else off_ref[0, 0] + k,
            col0=None if off_ref is None else off_ref[0, 1] + j * bn,
        )

    out_ref[...] = jax.lax.fori_loop(
        0, nk, body, jnp.zeros(out_ref.shape, jnp.float32)
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "spec", "io_bits", "adc_bits", "bb", "bn", "interpret", "transpose",
        "double_buffer", "dev",
    ),
)
def mvm_sliced_fused(
    planes: jax.Array,
    x: jax.Array,
    frac_bits: jax.Array,
    *,
    spec: SliceSpec,
    io_bits: int = 16,
    adc_bits: int | None = None,
    bb: int | None = None,
    bn: int = DEFAULT_BN,
    interpret: bool = False,
    transpose: bool = False,
    double_buffer: bool = True,
    dev=None,
    tile0=None,
    col0=None,
) -> jax.Array:
    """Quantize-fused sliced MVM: planes int8 [S,M,N]; x FLOAT [B,M]
    ([B,N] when ``transpose``); frac_bits int32 scalar DAC exponent ->
    f32 [B,N] ([B,M]) on the product grid.

    The DAC boundary lives inside the kernel: the float activation crosses
    HBM once and is quantized/bit-planed per tile in VMEM — no int operand
    or bit-plane array exists at the pallas_call boundary (jaxpr-asserted
    in tests). ``double_buffer=True`` selects the in-kernel crossbar-tile
    loop with 2-slot DMA prefetch of the digit planes; ``False`` keeps the
    3-D grid of ``mvm_sliced`` (used for equivalence testing and as the
    conservative fallback). ``bb=None`` takes the token block
    ``pick_token_block`` chooses; any ``bb`` gives the same bits.

    ``dev`` (static, a ``models.common.DeviceModel`` with ``read_noise > 0``)
    enables the frozen per-ADC-channel read offsets (module docstring);
    ``tile0``/``col0`` are the GLOBAL crossbar-tile / output-column offsets of
    this shard (int32 scalars, default 0) so sharded lowerings reproduce the
    single-host pattern. With ``dev=None`` no extra input exists and the
    compiled kernel is byte-identical to the pre-DeviceModel one.
    """
    S, M, N = planes.shape
    B = x.shape[0]
    contract, out_dim = (N, M) if transpose else (M, N)
    assert x.shape == (B, contract)
    assert contract % XBAR_ROWS == 0, (
        f"contraction dim {contract} must be a multiple of crossbar rows ({XBAR_ROWS})"
    )
    bn = pick_block(out_dim, bn)
    bb = (pick_token_block(B, bn, contract, spec, io_bits, adc_bits) if bb is None
          else pick_block(B, bb, granule=TOKEN_GRANULE))
    nk = contract // XBAR_ROWS
    noisy = dev is not None and dev.read_noise > 0.0
    f_spec = pl.BlockSpec(
        (1, 1), (lambda i, j: (0, 0)) if double_buffer else (lambda i, j, k: (0, 0)),
        memory_space=pltpu.SMEM,
    )
    f_arg = exp2i(frac_bits).reshape(1, 1)  # f32 DAC scale 2^frac_bits
    extra_specs, extra_args = [], []
    if noisy:
        off_spec = pl.BlockSpec(
            (1, 2), (lambda i, j: (0, 0)) if double_buffer else (lambda i, j, k: (0, 0)),
            memory_space=pltpu.SMEM,
        )
        extra_specs = [off_spec]
        extra_args = [
            jnp.stack([
                jnp.asarray(0 if tile0 is None else tile0, jnp.int32),
                jnp.asarray(0 if col0 is None else col0, jnp.int32),
            ]).reshape(1, 2)
        ]
    name = "panther_mvm_fused_t" if transpose else "panther_mvm_fused"

    if double_buffer:
        wshape = (2, S, bn, XBAR_ROWS) if transpose else (2, S, XBAR_ROWS, bn)
        return pl.pallas_call(
            functools.partial(
                _mvm_fused_db_kernel, spec=spec, io_bits=io_bits,
                adc_bits=adc_bits, nk=nk, bn=bn, transpose=transpose,
                dev=dev if noisy else None,
            ),
            grid=(B // bb, out_dim // bn),
            in_specs=[
                f_spec,
                pl.BlockSpec((bb, contract), lambda i, j: (i, 0)),
                pl.BlockSpec(memory_space=pl.ANY),  # full planes, DMA'd per tile
                *extra_specs,
            ],
            out_specs=pl.BlockSpec((bb, bn), lambda i, j: (i, j)),
            scratch_shapes=[
                pltpu.VMEM(wshape, jnp.int8),
                pltpu.SemaphoreType.DMA((2,)),
            ],
            out_shape=jax.ShapeDtypeStruct((B, out_dim), jnp.float32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel"),
                vmem_limit_bytes=VMEM_LIMIT,
            ),
            interpret=interpret,
            name=name + "_db",
        )(f_arg, x.astype(jnp.float32), planes, *extra_args)

    if transpose:
        plane_spec = pl.BlockSpec((S, bn, XBAR_ROWS), lambda i, j, k: (0, j, k))
    else:
        plane_spec = pl.BlockSpec((S, XBAR_ROWS, bn), lambda i, j, k: (0, k, j))
    return pl.pallas_call(
        functools.partial(
            _mvm_fused_kernel, spec=spec, io_bits=io_bits, adc_bits=adc_bits,
            nk=nk, transpose=transpose, dev=dev if noisy else None,
        ),
        grid=(B // bb, out_dim // bn, nk),
        in_specs=[
            f_spec,
            pl.BlockSpec((bb, XBAR_ROWS), lambda i, j, k: (i, k)),
            plane_spec,
            *extra_specs,
        ],
        out_specs=pl.BlockSpec((bb, bn), lambda i, j, k: (i, j)),
        scratch_shapes=[pltpu.VMEM((bb, bn), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((B, out_dim), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT,
        ),
        interpret=interpret,
        name=name,
    )(f_arg, x.astype(jnp.float32), planes, *extra_args)
