"""Public entry point for bit-exact sliced MVM / MᵀVM (fidelity path).

Dispatch policy (``use_kernel=None`` → auto): the Mosaic kernel engages on
TPU; on CPU the vectorized jnp reference runs — same packed bit-plane
schedule, value-equivalent (tested). ``transpose=True`` is the MᵀVM
(layer-gradient) read; it has a first-class kernel path (the seed fell back
to a Python-loop reference). Shapes whose contraction dim is not a multiple
of the 128-row crossbar fall back to the (ragged-capable) reference.

``mvm_sliced`` is the vector entry (one trailing contraction dim, one batch
dim). ``mvm_sliced_batched`` is the token-batched entry used by the training
forward/backward: arbitrary leading dims flatten into ONE token axis that
rides the kernel's batch grid, so every crossbar tile still issues one
``dot_general`` per bit-block — vmapping the vector entry over tokens would
shatter that operand back into per-token matmuls (the seed's 6%-MXU shape).

``mvm_sliced_fused`` / ``mvm_sliced_fused_batched`` are the quantize-fused
entries ``core.mvm.fidelity_read`` dispatches to: they take the FLOAT
activation plus the scalar DAC exponent and perform the ``io_bits``
round/saturate and bit-plane extraction inside the kernel (or inside the
jitted reference on the fallback path) — no quantized operand or bit-plane
array crosses the HBM boundary. Bit-identical to quantize → ``mvm_sliced``
composition (tested); the kernel path defaults to the double-buffered tile
DMA lowering (see ``kernel.py``).

``mvm_sliced_sharded`` is the mesh lowering of the batched entry: a
shard_map whose token axis shards over the data-parallel axes and whose
crossbar row/column tile blocks shard over the tensor-parallel 'model' axis,
each shard running the identical packed schedule on its local tiles. When
the *contraction* side is sharded (forward read of a row-parallel weight,
MᵀVM read of a column-parallel one) the per-shard shift-and-add partials are
psum-reduced exactly (``distributed.collectives.tile_psum``) — the crossbar
tiling makes this lossless: ADC quantization is per 128-row tile, so as long
as every shard holds whole tiles the sharded read computes the same tile
currents as the single-host schedule and only the final (exact-in-the-
f32-regime) accumulation is distributed.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.slicing import SliceSpec
from . import kernel as _k
from . import ref as _ref


def _normalize_read_device(device):
    """None unless the read path is non-ideal (an ideal or write-only
    DeviceModel must compile the exact ideal read kernel)."""
    if device is None or not device.reads_nonideal():
        return None
    return device


def mvm_sliced(
    planes,
    x_q,
    spec: SliceSpec,
    *,
    io_bits: int = 16,
    adc_bits: int | None = None,
    transpose: bool = False,
    use_kernel: bool | None = None,
    interpret: bool | None = None,
):
    on_tpu = jax.default_backend() == "tpu"
    if use_kernel is None:
        use_kernel = on_tpu
    if interpret is None:
        interpret = not on_tpu
    contract = planes.shape[2] if transpose else planes.shape[1]
    if not use_kernel or contract % _k.XBAR_ROWS != 0:
        return _ref.mvm_sliced_ref(
            planes, x_q, spec, io_bits, adc_bits, transpose=transpose
        )
    return _k.mvm_sliced(
        planes, x_q, spec=spec, io_bits=io_bits, adc_bits=adc_bits,
        interpret=interpret, transpose=transpose,
    )


def mvm_sliced_fused(
    planes,
    x,
    frac_bits,
    spec: SliceSpec,
    *,
    io_bits: int = 16,
    adc_bits: int | None = None,
    transpose: bool = False,
    use_kernel: bool | None = None,
    interpret: bool | None = None,
    double_buffer: bool | None = None,
    device=None,
    tile0=None,
    col0=None,
):
    """Quantize-fused vector entry: ``x`` FLOAT [B, M] ([B, N] when
    ``transpose``) plus the int32 DAC exponent ``frac_bits`` -> f32 on the
    product grid. The ``io_bits`` DAC quantize and bit-plane extraction
    happen inside the kernel (or inside the fused reference) — callers never
    materialise the integer operand. ``double_buffer`` picks the in-kernel
    crossbar-tile loop with 2-slot DMA prefetch (default on the kernel path);
    ``False`` keeps the 3-D grid for equivalence testing. ``device`` (a
    ``models.common.DeviceModel`` with ``read_noise > 0``) injects the frozen
    per-ADC-channel read offsets; ``tile0``/``col0`` are the global crossbar-
    tile / output-column offsets of a shard (default 0).
    """
    on_tpu = jax.default_backend() == "tpu"
    if use_kernel is None:
        use_kernel = on_tpu
    if interpret is None:
        interpret = not on_tpu
    device = _normalize_read_device(device)
    contract = planes.shape[2] if transpose else planes.shape[1]
    if not use_kernel or contract % _k.XBAR_ROWS != 0:
        return _ref.mvm_sliced_fused_ref(
            planes, x, jnp.asarray(frac_bits, jnp.int32), spec, io_bits,
            adc_bits, transpose=transpose, device=device,
            tile0=0 if tile0 is None else tile0,
            col0=0 if col0 is None else col0,
        )
    return _k.mvm_sliced_fused(
        planes, x, frac_bits, spec=spec, io_bits=io_bits, adc_bits=adc_bits,
        interpret=interpret, transpose=transpose,
        double_buffer=True if double_buffer is None else double_buffer,
        dev=device, tile0=tile0, col0=col0,
    )


def mvm_sliced_fused_batched(
    planes,
    x,
    frac_bits,
    spec: SliceSpec,
    *,
    io_bits: int = 16,
    adc_bits: int | None = None,
    transpose: bool = False,
    use_kernel: bool | None = None,
    interpret: bool | None = None,
    double_buffer: bool | None = None,
    device=None,
    tile0=None,
    col0=None,
):
    """Token-batched quantize-fused read: FLOAT ``x`` [..., M] ([..., N] when
    ``transpose``), arbitrary leading dims flattened into one token axis (see
    ``mvm_sliced_batched``). Zero padding rows quantize to zero (round(0)=0)
    ⇒ all-zero bit planes, so padding stays value-inert on the fused path too
    (the device read offsets are per output column — identical on every
    token row, padding included).
    """
    contract = planes.shape[2] if transpose else planes.shape[1]
    lead = x.shape[:-1]
    assert x.shape[-1] == contract, (x.shape, planes.shape, transpose)
    x2 = x.reshape(-1, contract)
    t = x2.shape[0]
    # pad to the kernel's 8-row token granule (pick_token_block would
    # otherwise degrade to tiny odd blocks for prime token counts)
    pad = (-t) % _k.TOKEN_GRANULE
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    out = mvm_sliced_fused(
        planes, x2, frac_bits, spec, io_bits=io_bits, adc_bits=adc_bits,
        transpose=transpose, use_kernel=use_kernel, interpret=interpret,
        double_buffer=double_buffer, device=device, tile0=tile0, col0=col0,
    )
    if pad:
        out = out[:t]
    return out.reshape(*lead, out.shape[-1])


def mvm_sliced_batched(
    planes,
    x_q,
    spec: SliceSpec,
    *,
    io_bits: int = 16,
    adc_bits: int | None = None,
    transpose: bool = False,
    use_kernel: bool | None = None,
    interpret: bool | None = None,
):
    """Token-batched sliced MVM / MᵀVM: ``x_q`` int [..., M] (or [..., N]
    when ``transpose``) with arbitrary leading dims -> f32 [..., N] ([..., M]).

    All leading dims flatten into one token axis of the 2-D engine — the
    kernel grid tiles it in token blocks of up to ``kernel.BB_CAP`` rows
    (``kernel.pick_token_block``), so each crossbar tile issues one
    ``[(io_bits-1)·bb, 128]`` MXU operand per token block (one dot per tile;
    jaxpr-asserted in tests) whatever the token count. Each output
    row depends only on its own input row and the ADC applies elementwise,
    so the flattened form is bit-identical to per-token vector reads
    (property-tested); zero padding rows (sign 0 ⇒ all-zero bit planes) are
    sliced back off without touching real rows.
    """
    contract = planes.shape[2] if transpose else planes.shape[1]
    lead = x_q.shape[:-1]
    assert x_q.shape[-1] == contract, (x_q.shape, planes.shape, transpose)
    x2 = x_q.reshape(-1, contract)
    t = x2.shape[0]
    pad = (-t) % _k.TOKEN_GRANULE
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    out = mvm_sliced(
        planes, x2, spec, io_bits=io_bits, adc_bits=adc_bits, transpose=transpose,
        use_kernel=use_kernel, interpret=interpret,
    )
    if pad:
        out = out[:t]
    return out.reshape(*lead, out.shape[-1])


def mvm_sliced_sharded(
    planes,
    x_q,
    spec: SliceSpec,
    *,
    mesh,
    data_axes: tuple = (),
    model_axis: str | None = None,
    shard_dim: int | None = None,
    io_bits: int = 16,
    adc_bits: int | None = None,
    transpose: bool = False,
    use_kernel: bool | None = None,
    interpret: bool | None = None,
    frac_bits=None,
    device=None,
):
    """Mesh-sharded token-batched sliced MVM / MᵀVM (module docstring).

    ``planes`` int8 [S, M, N] (one layer's digit planes — no stack dims);
    ``x_q`` int [..., M] ([..., N] when ``transpose``). With
    ``frac_bits`` (int32 scalar DAC exponent) the entry is the quantize-FUSED
    read: ``x_q`` is then the FLOAT activation and every shard runs the fused
    kernel locally. The exponent itself was chosen *globally* by the caller
    (``choose_frac_bits`` before the shard_map) and enters replicated, so
    each shard quantizes against the same DAC range and the sharded fused
    read equals the single-host one. ``data_axes`` are the
    mesh axes the flattened token axis shards over; ``model_axis`` names the
    tensor-parallel axis and ``shard_dim`` which matrix dim of the dense
    ``[M, N]`` weight it carries (``FidelityConfig.shard_dim``: 0 = rows,
    1 = columns, ``None`` = replicated planes, token sharding only).

    Alignment guards (static, trace-time): a sharded *contraction* dim must
    split into whole 128-row crossbar tiles per shard at finite ADC (the ADC
    boundary is per tile — a misaligned split would quantize different tile
    sums than the single-host schedule) and merely divide evenly at
    ``adc_bits=None`` (ideal-ADC streaming is linear in row blocks); a
    sharded *output* dim must divide evenly. Unmet guards drop the model-
    axis sharding for this read (tokens stay sharded) rather than change
    numerics — equivalence to the single-host schedule is the contract.

    ``device`` (read-noisy ``DeviceModel``, fused entry only) reproduces the
    single-host frozen ADC-channel offsets: each shard derives its global
    crossbar-tile / output-column offsets from ``axis_index(model_axis)``.
    Because the offsets are a function of the 128-row tile index, a read-
    noisy sharded *contraction* must split into whole tiles even at
    ``adc_bits=None`` — the granule guard tightens accordingly.
    """
    contract = planes.shape[2] if transpose else planes.shape[1]
    out_dim = planes.shape[1] if transpose else planes.shape[2]
    lead = x_q.shape[:-1]
    assert planes.ndim == 3 and x_q.shape[-1] == contract, (planes.shape, x_q.shape)
    device = _normalize_read_device(device)

    dp = tuple(a for a in data_axes if a in mesh.axis_names and mesh.shape[a] > 1)
    dsize = 1
    for a in dp:
        dsize *= mesh.shape[a]
    maxis = model_axis if (model_axis in mesh.axis_names and mesh.shape[model_axis] > 1) else None
    msize = mesh.shape[maxis] if maxis is not None else 1

    sd = shard_dim if maxis is not None else None
    if sd is not None:
        if sd == (1 if transpose else 0):  # contraction side sharded
            granule = (
                msize if adc_bits is None and device is None
                else msize * _k.XBAR_ROWS
            )
            if contract % granule != 0:
                sd = None
        elif out_dim % msize != 0:  # output side sharded
            sd = None
    if not dp and sd is None:
        # 1-device (or unusable) mesh: the plain batched entry IS the lowering
        if frac_bits is not None:
            return mvm_sliced_fused_batched(
                planes, x_q, frac_bits, spec, io_bits=io_bits, adc_bits=adc_bits,
                transpose=transpose, use_kernel=use_kernel, interpret=interpret,
                device=device,
            )
        return mvm_sliced_batched(
            planes, x_q, spec, io_bits=io_bits, adc_bits=adc_bits,
            transpose=transpose, use_kernel=use_kernel, interpret=interpret,
        )

    from jax.sharding import PartitionSpec as P

    x2 = x_q.reshape(-1, contract)
    t = x2.shape[0]
    # pad so every data shard lands on the kernel's token granule
    pad = (-t) % (_k.TOKEN_GRANULE * dsize)
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))

    contract_sharded = sd == (1 if transpose else 0)
    out_sharded = sd == (0 if transpose else 1)
    dp_entry = dp if len(dp) > 1 else (dp[0] if dp else None)
    w_spec = [None, None, None]
    if sd is not None:
        w_spec[1 + sd] = maxis

    def local(planes_l, x_l, f_l):
        tile0 = col0 = None
        if device is not None and maxis is not None and sd is not None:
            # global coordinates of this shard's tiles/columns, so the frozen
            # read-offset pattern matches the single-host schedule exactly
            idx = jax.lax.axis_index(maxis)
            if contract_sharded:
                tile0 = idx * ((contract // msize) // _k.XBAR_ROWS)
            elif out_sharded:
                col0 = idx * (out_dim // msize)
        if frac_bits is not None:
            acc = mvm_sliced_fused(
                planes_l, x_l, f_l, spec, io_bits=io_bits, adc_bits=adc_bits,
                transpose=transpose, use_kernel=use_kernel, interpret=interpret,
                device=device, tile0=tile0, col0=col0,
            )
        else:
            acc = mvm_sliced(
                planes_l, x_l, spec, io_bits=io_bits, adc_bits=adc_bits,
                transpose=transpose, use_kernel=use_kernel, interpret=interpret,
            )
        if contract_sharded:
            from repro.distributed.collectives import tile_psum  # lazy: no cycle

            acc = tile_psum(acc, maxis)
        return acc

    # the DAC exponent rides along replicated (P()); a dummy zero keeps the
    # shard_map signature static on the unfused path
    f_arg = jnp.asarray(0 if frac_bits is None else frac_bits, jnp.int32)
    out = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(
            P(*w_spec),
            P(dp_entry, maxis if contract_sharded else None),
            P(),
        ),
        out_specs=P(dp_entry, maxis if out_sharded else None),
        check_vma=False,
    )(planes, x2, f_arg)
    if pad:
        out = out[:t]
    return out.reshape(*lead, out.shape[-1])
