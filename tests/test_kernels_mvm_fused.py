"""Tests for the quantize-fused sliced-MVM entry and the no-HBM-crossing
contract of the fused DAC boundary.

Invariants:

* the in-kernel/in-ref DAC prologue is bit-identical to
  ``core.fixed_point.quantize`` (same round/saturate arithmetic, same exact
  power-of-two scale via ``exp2i``);
* at ``adc_bits=None`` the fused entries are bit-identical to the unfused
  quantize-then-read composition (the ideal branch keeps the exact op
  order); at finite ADC the restructured fold stays within the established
  kernel-vs-ref tolerance;
* the double-buffered DMA lowering computes the same numbers as the 3-D
  grid lowering (bit-identical: same per-tile compute in the same k order);
* any token block gives the bits of 8-row blocks (each output row reads
  only its own input row), and the chosen block fits the VMEM limit;
* NOTHING quantized crosses the pallas_call boundary: no int32 operand, no
  bit-plane stack, no noise grid — jaxpr-audited via
  ``kernels.common.forbid_pallas_inputs``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.fixed_point import choose_frac_bits, counter_key_scalars, exp2i, quantize
from repro.core.slicing import DEFAULT_SPEC
from repro.kernels.common import forbid_pallas_inputs, pallas_input_avals
from repro.kernels.sliced_mvm import kernel as K
from repro.kernels.sliced_mvm import ops as O
from repro.kernels.sliced_mvm import ref as R

SPEC = DEFAULT_SPEC
IO_BITS = 16


def _case(m=256, n=192, b=16, seed=0):
    rng = np.random.default_rng(seed)
    planes = jnp.asarray(
        rng.integers(-7, 8, size=(SPEC.n_slices, m, n)), jnp.int8
    )
    x = jnp.asarray(rng.normal(size=(b, m)), jnp.float32)
    xt = jnp.asarray(rng.normal(size=(b, n)), jnp.float32)
    return planes, x, xt


def _xf(x):
    return choose_frac_bits(x, word_bits=IO_BITS, margin_bits=2, clip_to_word=False)


def test_dac_quantize_matches_quantize():
    _, x, _ = _case()
    xf = _xf(x)
    assert jnp.array_equal(
        R.dac_quantize(x, xf, IO_BITS), quantize(x, xf, word_bits=IO_BITS)
    )
    # saturation: values beyond the word rail at +/-(2^(io-1)-1)
    big = jnp.asarray([[1e9, -1e9]], jnp.float32)
    q = R.dac_quantize(big, jnp.int32(0), IO_BITS)
    lim = 2 ** (IO_BITS - 1) - 1
    assert q.tolist() == [[lim, -lim]]


@pytest.mark.parametrize("transpose", [False, True])
def test_fused_ref_ideal_bit_identical_to_unfused(transpose):
    planes, x, xt = _case()
    xx = xt if transpose else x
    xf = _xf(xx)
    xq = quantize(xx, xf, word_bits=IO_BITS)
    old = R.mvm_sliced_ref(planes, xq, SPEC, IO_BITS, None, transpose=transpose)
    fused = R.mvm_sliced_fused_ref(planes, xx, xf, SPEC, IO_BITS, None,
                                   transpose=transpose)
    assert jnp.array_equal(old, fused)


@pytest.mark.parametrize("transpose", [False, True])
@pytest.mark.parametrize("adc_bits", [9, 6])
def test_fused_ref_finite_adc_close_to_unfused(transpose, adc_bits):
    planes, x, xt = _case()
    xx = xt if transpose else x
    xf = _xf(xx)
    xq = quantize(xx, xf, word_bits=IO_BITS)
    old = R.mvm_sliced_ref(planes, xq, SPEC, IO_BITS, adc_bits, transpose=transpose)
    fused = R.mvm_sliced_fused_ref(planes, xx, xf, SPEC, IO_BITS, adc_bits,
                                   transpose=transpose)
    tol = 1e-3 * (1.0 + float(jnp.abs(old).max()))
    assert float(jnp.abs(old - fused).max()) <= tol


@pytest.mark.parametrize("transpose", [False, True])
@pytest.mark.parametrize("adc_bits", [None, 9])
@pytest.mark.parametrize("double_buffer", [False, True])
def test_fused_kernel_bit_identical_to_unfused_kernel(transpose, adc_bits,
                                                      double_buffer):
    # the fused kernel = in-kernel DAC + the SAME tile compute in the same
    # tile order as the unfused kernel fed pre-quantized ints -> bit-exact
    planes, x, xt = _case(m=256, n=256, b=16)
    xx = xt if transpose else x
    xf = _xf(xx)
    xq = quantize(xx, xf, word_bits=IO_BITS)
    unfused = O.mvm_sliced(planes, xq, SPEC, io_bits=IO_BITS, adc_bits=adc_bits,
                           transpose=transpose, use_kernel=True, interpret=True)
    fused = O.mvm_sliced_fused(planes, xx, xf, SPEC, io_bits=IO_BITS,
                               adc_bits=adc_bits, transpose=transpose,
                               use_kernel=True, interpret=True,
                               double_buffer=double_buffer)
    assert jnp.array_equal(unfused, fused)


@pytest.mark.parametrize("adc_bits", [None, 9])
def test_fused_kernel_close_to_fused_ref(adc_bits):
    planes, x, _ = _case(m=384, n=256, b=24)
    xf = _xf(x)
    ref = R.mvm_sliced_fused_ref(planes, x, xf, SPEC, IO_BITS, adc_bits)
    for db in (False, True):
        out = O.mvm_sliced_fused(planes, x, xf, SPEC, io_bits=IO_BITS,
                                 adc_bits=adc_bits, use_kernel=True,
                                 interpret=True, double_buffer=db)
        tol = 1e-3 * (1.0 + float(jnp.abs(ref).max()))
        assert float(jnp.abs(out - ref).max()) <= tol


def test_fused_batched_ragged_leading_dims():
    planes, _, _ = _case()
    x = jnp.asarray(np.random.default_rng(5).normal(size=(3, 5, 256)), jnp.float32)
    xf = _xf(x)
    out = O.mvm_sliced_fused_batched(planes, x, xf, SPEC, io_bits=IO_BITS,
                                     adc_bits=9, use_kernel=True, interpret=True)
    ref = R.mvm_sliced_fused_ref(planes, x.reshape(-1, 256), xf, SPEC, IO_BITS, 9)
    tol = 1e-3 * (1.0 + float(jnp.abs(ref).max()))
    assert out.shape == (3, 5, 192)
    assert float(jnp.abs(out.reshape(-1, 192) - ref).max()) <= tol


def test_fidelity_read_fused_equals_unfused_composition():
    # end-to-end: fidelity_read (now fused) == the pre-fusion composition
    # quantize -> batched integer read -> rescale, bit-identical at ideal ADC
    from repro.core.mvm import fidelity_read
    from repro.kernels.sliced_mvm import mvm_sliced_batched

    planes, x, _ = _case()

    class Fid:
        spec = SPEC
        io_bits = IO_BITS
        margin_bits = 2
        adc_bits_fwd = None
        adc_bits_bwd = None
        shard_dim = None
        use_kernel = None
        interpret = None

    F = jnp.int32(10)
    y = fidelity_read(planes, F, x, Fid())
    xf = _xf(x)
    xq = quantize(x, xf, word_bits=IO_BITS)
    y_old = mvm_sliced_batched(planes, xq, SPEC, io_bits=IO_BITS,
                               adc_bits=None) * exp2i(-(xf + F))
    assert jnp.array_equal(y, y_old)


# ---------------------------------------------------------------------------
# token blocks: one plane tile shared by up to BB_CAP token rows
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("tokens", [8, 200, 512])
@pytest.mark.parametrize("adc_bits", [9, None], ids=["adc9", "ideal"])
@pytest.mark.parametrize("transpose", [False, True], ids=["mvm", "mtvm"])
@pytest.mark.parametrize("noisy", [False, True], ids=["exact", "read_noise"])
def test_token_block_bit_identical_to_bb8(tokens, adc_bits, transpose, noisy):
    """The double-buffered read at the chosen token block (512 -> 128, 200 ->
    its divisor 40, 8 -> 8) gives the bits of the same read at bb = 8: each
    output row reads only its own input row, in the same tile order."""
    from repro.models.common import DeviceModel

    planes, _, _ = _case(m=256, n=256, b=1)
    x = jnp.asarray(np.random.default_rng(tokens).normal(size=(tokens, 256)), jnp.float32)
    dev = DeviceModel(read_noise=0.05, stuck_seed=3) if noisy else None
    xf = _xf(x)
    bb = K.pick_token_block(tokens, 256, 256, SPEC, IO_BITS, adc_bits)
    assert bb == {8: 8, 200: 40, 512: 128}[tokens]

    def read(**kw):
        return K.mvm_sliced_fused(planes, x, xf, spec=SPEC, io_bits=IO_BITS,
                                  adc_bits=adc_bits, transpose=transpose,
                                  interpret=True, dev=dev, **kw)

    np.testing.assert_array_equal(
        np.asarray(read()).view(np.uint32), np.asarray(read(bb=8)).view(np.uint32))


PHI4_READS = {  # [M, N] of the five projections a phi4-mini layer reads
    "wqkv": (3072, 5120), "attn_wo": (3072, 3072), "wi_gate": (3072, 8192),
    "wi_up": (3072, 8192), "mlp_wo": (8192, 3072),
}


@pytest.mark.parametrize("adc_bits", [9, None], ids=["adc9", "ideal"])
@pytest.mark.parametrize("transpose", [False, True], ids=["mvm", "mtvm"])
@pytest.mark.parametrize("read", sorted(PHI4_READS))
def test_token_block_at_phi4_widths(read, transpose, adc_bits):
    """A 512-token step (2 x 256) reads every phi4-mini projection in 128-row
    token blocks, and their buffers fit the VMEM limit."""
    m, n = PHI4_READS[read]
    contract, out_dim = (n, m) if transpose else (m, n)
    bn = K.DEFAULT_BN
    assert out_dim % bn == 0
    assert K.pick_token_block(512, bn, contract, SPEC, IO_BITS, adc_bits) == K.BB_CAP == 128
    assert K.read_vmem_bytes(128, bn, contract, SPEC, IO_BITS, adc_bits) <= K.VMEM_LIMIT


@pytest.mark.parametrize("tokens,contract,adc_bits,want", [
    (1, 3072, 9, 1),        # decode: one block of the whole batch
    (4, 3072, 9, 4),
    (16, 3072, 9, 16),      # up to the cap the block is the batch
    (128, 3072, 9, 128),
    (12, 3072, 9, 6),       # off the 8-row granule: pick_block's fallback
    (200, 3072, 9, 40),     # the largest granule divisor under the cap
    (1024, 3072, 9, 128),   # the cap
    (4096, 8192, None, 128),
    (512, 22016, 9, 64),    # a strip this wide: the VMEM limit binds
    (512, 65536, 9, 32),
    (512, 131072, 9, 16),
])
def test_pick_token_block(tokens, contract, adc_bits, want):
    bb = K.pick_token_block(tokens, K.DEFAULT_BN, contract, SPEC, IO_BITS, adc_bits)
    assert bb == want
    assert tokens % bb == 0


# ---------------------------------------------------------------------------
# no-HBM-crossing contract (the tentpole's jaxpr audit)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("transpose", [False, True])
@pytest.mark.parametrize("double_buffer", [False, True])
def test_no_quantized_operand_crosses_hbm(transpose, double_buffer):
    # contract dim must be tile-aligned both ways or ops falls back to ref
    planes, x, xt = _case(m=256, n=256, b=16)
    xx = xt if transpose else x
    B, contract = xx.shape
    xf = jnp.int32(11)

    def fused(p, a, f):
        return O.mvm_sliced_fused(p, a, f, SPEC, io_bits=IO_BITS, adc_bits=9,
                                  transpose=transpose, use_kernel=True,
                                  interpret=True, double_buffer=double_buffer)

    avals = forbid_pallas_inputs(
        fused, planes, xx, xf,
        forbidden=[
            ((B, contract), "int32"),                # quantized operand
            ((IO_BITS - 1, B, contract), "int32"),   # bit-plane stack
            ((IO_BITS - 1, B, contract), "float32"),
        ],
    )
    # the boundary carries exactly: SMEM DAC scale 2^F, float activation, planes
    shapes = sorted((tuple(a.shape), str(a.dtype)) for a in avals)
    assert ((B, contract), "float32") in shapes
    assert ((1, 1), "float32") in shapes


def test_no_noise_grid_crosses_hbm():
    # counter-mode stochastic OPA: only two key words enter (SMEM); the
    # legacy grid mode is the one that ships an [M, N] noise array
    from repro.kernels.sliced_opa.ops import opa_fused_update

    m, n, t = 128, 192, 256
    rng = np.random.default_rng(1)
    planes = jnp.asarray(rng.integers(-7, 8, size=(SPEC.n_slices, m, n)), jnp.int8)
    x = jnp.asarray(rng.normal(size=(t, m)), jnp.float32)
    dh = jnp.asarray(rng.normal(size=(t, n)), jnp.float32)
    key = jax.random.PRNGKey(2)

    def upd(p, a, b, k):
        return opa_fused_update(p, a, b, jnp.float32(0.05), jnp.int32(20), SPEC,
                                stochastic=True, key=k, rng_mode="counter",
                                use_kernel=True, interpret=True)

    avals = forbid_pallas_inputs(
        upd, planes, x, dh, key, forbidden=[((m, n), "float32")]
    )
    assert ((1, 2), "int32") in [(tuple(a.shape), str(a.dtype)) for a in avals]

    # grid mode DOES ship the noise grid (the audited legacy behaviour)
    def upd_grid(p, a, b, k):
        return opa_fused_update(p, a, b, jnp.float32(0.05), jnp.int32(20), SPEC,
                                stochastic=True, key=k, rng_mode="grid",
                                use_kernel=True, interpret=True)

    grid_avals = pallas_input_avals(upd_grid, planes, x, dh, key)
    assert ((m, n), "float32") in [(tuple(a.shape), str(a.dtype)) for a in grid_avals]
