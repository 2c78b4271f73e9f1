"""Pallas sliced-MVM kernel vs pure-jnp oracles: shape/dtype/ADC sweeps,
the MᵀVM (transpose) path, the packed-schedule dot-count acceptance, and the
code-domain ADC's bit identity with the current-domain epilogue."""
import zlib

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import DEFAULT_SPEC, SliceSpec, slice_weights
from repro.core.mvm import _adc
from repro.core.slicing import LOGICAL_BITS
from repro.kernels.sliced_mvm import kernel as K
from repro.kernels.sliced_mvm import mvm_sliced
from repro.kernels.sliced_mvm.kernel import tile_dot_count
from repro.kernels.sliced_mvm.ref import mvm_sliced_looped, mvm_sliced_ref
from repro.models.common import DeviceModel

SPECS = [DEFAULT_SPEC, SliceSpec.uniform(6)]
CASES = [
    # (M, N, B)
    (128, 128, 1),
    (256, 384, 8),
    (384, 128, 16),
    (512, 256, 4),
]


def _data(spec, m, n, b, contract, seed, io_bits=16):
    if not isinstance(seed, int):
        # deterministic across interpreter runs (unlike salted hash()) so any
        # tolerance failure reproduces
        seed = zlib.crc32(repr(seed).encode())
    rng = np.random.default_rng(seed % 2**31)
    q = jnp.asarray(rng.integers(-(2**26), 2**26, size=(m, n)), jnp.int32)
    planes = slice_weights(q, spec)
    # full sign-magnitude range (inclusive): the top bit plane (t=io_bits-2)
    # must actually be exercised
    hi = 2 ** (io_bits - 1) - 1
    x = jnp.asarray(rng.integers(-hi, hi + 1, size=(b, contract)), jnp.int32)
    return q, planes, x


@pytest.mark.parametrize("spec", SPECS, ids=lambda s: s.name())
@pytest.mark.parametrize("mnb", CASES, ids=str)
@pytest.mark.parametrize("adc_bits", [None, 12, 9], ids=["ideal", "adc12", "adc9"])
@pytest.mark.parametrize("transpose", [False, True], ids=["fwd", "mtvm"])
def test_mvm_kernel_matches_ref(spec, mnb, adc_bits, transpose):
    m, n, b = mnb
    _, planes, x = _data(
        spec, m, n, b, n if transpose else m, (spec.name(), mnb, adc_bits, transpose)
    )
    yk = np.asarray(
        mvm_sliced(planes, x, spec, adc_bits=adc_bits, transpose=transpose,
                   use_kernel=True, interpret=True),
        np.float64,
    )
    yr = np.asarray(
        mvm_sliced_ref(planes, x, spec, adc_bits=adc_bits, transpose=transpose), np.float64
    )
    np.testing.assert_allclose(yk, yr, rtol=1e-6, atol=1e-3 * (1 + np.abs(yr).max()))


@pytest.mark.parametrize("mnb", CASES[:2], ids=str)
@pytest.mark.parametrize("transpose", [False, True], ids=["fwd", "mtvm"])
def test_ideal_adc_equals_dequant_matmul(mnb, transpose):
    """Kernel @ adc=None == dequantize->matmul: the production fast path is
    bit-faithful to the crossbar model (DESIGN.md §4) — both read directions."""
    m, n, b = mnb
    spec = DEFAULT_SPEC
    q, planes, x = _data(spec, m, n, b, n if transpose else m, 11)
    yk = np.asarray(
        mvm_sliced(planes, x, spec, adc_bits=None, transpose=transpose,
                   use_kernel=True, interpret=True),
        np.float64,
    )
    qd = np.asarray(q, np.float64)
    ref = np.asarray(x, np.float64) @ (qd.T if transpose else qd)
    np.testing.assert_allclose(yk, ref, rtol=1e-6, atol=1e-5 * (1 + np.abs(ref).max()))


def test_adc_error_shrinks_with_resolution():
    """Finite-ADC error is monotone in resolution (sanity of fidelity model)."""
    m, n, b = 256, 256, 4
    spec = DEFAULT_SPEC
    q, planes, x = _data(spec, m, n, b, m, 13)
    exact = np.asarray(x, np.float64) @ np.asarray(q, np.float64)
    errs = []
    for adc in (8, 10, 12):
        y = np.asarray(
            mvm_sliced(planes, x, spec, adc_bits=adc, use_kernel=True, interpret=True),
            np.float64,
        )
        errs.append(np.abs(y - exact).mean())
    assert errs[0] >= errs[1] >= errs[2]


@pytest.mark.parametrize("spec", SPECS, ids=lambda s: s.name())
@pytest.mark.parametrize("io_bits", [8, 16])
@pytest.mark.parametrize("adc_bits", [None, 6, 9], ids=["ideal", "adc6", "adc9"])
@pytest.mark.parametrize("transpose", [False, True], ids=["fwd", "mtvm"])
def test_packed_tile_issues_at_most_S_dots(spec, io_bits, adc_bits, transpose):
    """Acceptance: the packed kernel issues <= S dot_generals per crossbar
    tile (the seed schedule issued S*(io_bits-1) = up to 120). The count is
    taken from the jaxpr of the exact tile body the Pallas kernel runs."""
    n = tile_dot_count(spec, io_bits, adc_bits, transpose=transpose)
    assert n <= spec.n_slices, n
    assert n == 1  # the packed schedule is a single full-width contraction


def test_ragged_shapes_fall_back_to_ref():
    """Contraction dims off the 128 crossbar granule dispatch to the (ragged-
    capable) reference instead of tripping the kernel's alignment assert."""
    spec = DEFAULT_SPEC
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.integers(-(2**20), 2**20, size=(160, 96)), jnp.int32)
    planes = slice_weights(q, spec)
    x = jnp.asarray(rng.integers(-(2**10), 2**10, size=(2, 160)), jnp.int32)
    y = np.asarray(mvm_sliced(planes, x, spec, adc_bits=9, use_kernel=True, interpret=True))
    yr = np.asarray(mvm_sliced_ref(planes, x, spec, adc_bits=9))
    np.testing.assert_allclose(y, yr, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("transpose", [False, True], ids=["fwd", "mtvm"])
def test_packed_ref_matches_looped_full_range(transpose):
    """Packed ref vs the seed per-(s,t) serial oracle at full 16-bit input
    range (f32 accumulation-order differences only)."""
    spec = DEFAULT_SPEC
    m, n, b = 256, 256, 4
    _, planes, x = _data(spec, m, n, b, n if transpose else m, 17)
    for adc in (None, 6, 9):
        yp = np.asarray(mvm_sliced_ref(planes, x, spec, 16, adc, transpose=transpose), np.float64)
        yl = np.asarray(mvm_sliced_looped(planes, x, spec, 16, adc, transpose=transpose), np.float64)
        np.testing.assert_allclose(yp, yl, rtol=1e-6, atol=1e-3 * (1 + np.abs(yl).max()))


# --- the finite-ADC tile body in the code domain -----------------------------

ADC_SPECS = [DEFAULT_SPEC, SliceSpec.uniform(8), SliceSpec.uniform(2)]
TILE_BB, TILE_BN = 8, 128


def _float_epilogue(y, spec, io_bits, adc_bits, bb, bn):
    """The current-domain ADC epilogue the code-domain one replaced:
    ``core.mvm._adc`` on the stacked currents, then the 2^t and 16^s folds."""
    S = spec.n_slices
    fs = jnp.concatenate(
        [jnp.full((1, bn), float(K.XBAR_ROWS * spec.plane_max[s]), jnp.float32)
         for s in range(S)], axis=1)
    y = _adc(y, fs, adc_bits)
    z = y[0:bb]
    for t in range(1, io_bits - 1):
        z = z + y[t * bb:(t + 1) * bb] * float(2**t)
    acc = z[:, 0:bn]
    for s in range(1, S):
        acc = acc + z[:, s * bn:(s + 1) * bn] * float(2 ** (LOGICAL_BITS * s))
    return acc


def _float_tile(xq, w, spec, io_bits, adc_bits, transpose, dev, tile_idx, col0):
    """The whole finite-ADC tile body with the current-domain epilogue."""
    S = spec.n_slices
    axis, dims = (0, (((1,), (1,)), ((), ()))) if transpose else (1, (((1,), (0,)), ((), ())))
    bn = w.shape[1] if transpose else w.shape[2]
    w_cat = jnp.concatenate([w[s].astype(jnp.float32) for s in range(S)], axis=axis)
    sx, mx = jnp.sign(xq), jnp.abs(xq)
    xp = jnp.concatenate([((mx >> t) & 1) * sx for t in range(io_bits - 1)], axis=0)
    y = jax.lax.dot_general(xp.astype(jnp.bfloat16), w_cat.astype(jnp.bfloat16), dims,
                            preferred_element_type=jnp.float32)
    if dev is not None:
        y = y + K.read_offsets(dev, spec, tile_idx, col0, bn, transpose)
    return _float_epilogue(y, spec, io_bits, adc_bits, xq.shape[0], bn)


def _bits(a):
    return np.asarray(a, np.float32).view(np.uint32)


@pytest.mark.parametrize("spec", ADC_SPECS, ids=lambda s: s.name())
@pytest.mark.parametrize("adc_bits", [1, 4, 8, 9, 12, 16])
@pytest.mark.parametrize("noisy", [False, True], ids=["exact", "read_noise"])
@pytest.mark.parametrize("transpose", [False, True], ids=["fwd", "mtvm"])
def test_code_domain_adc_bit_identical(spec, adc_bits, noisy, transpose):
    """The code-domain ADC (round/clip on y/step, step folded into the 16^s
    constants) gives the current-domain epilogue's bits: on integer currents
    over the whole ±128·128 range, with and without read-noise offsets, and
    through the whole tile body (digits prescaled by 1/step) in both read
    directions."""
    S, bb, bn = spec.n_slices, TILE_BB, TILE_BN
    rng = np.random.default_rng(zlib.crc32(repr((spec.name(), adc_bits, noisy, transpose)).encode()))
    dev = DeviceModel(read_noise=0.05, stuck_seed=7) if noisy else None
    # the epilogue alone, on currents up to the largest a 128-row tile sums
    y = jnp.asarray(rng.integers(-128 * 128, 128 * 128 + 1, size=(15 * bb, S * bn)), jnp.float32)
    if noisy:
        y = y + K.read_offsets(dev, spec, 3, 2 * bn, bn, transpose)
    inv_step = jnp.concatenate(
        [jnp.full((1, bn), 1.0 / st, jnp.float32) for st in K.adc_steps(spec, adc_bits)], axis=1)
    got = K._adc_fold(y * inv_step, spec=spec, io_bits=16, adc_bits=adc_bits, bb=bb, bn=bn)
    want = _float_epilogue(y, spec, 16, adc_bits, bb, bn)
    np.testing.assert_array_equal(_bits(got), _bits(want))
    # the tile body: any int8 digit (|d| <= 128), the full 16-bit DAC range
    wshape = (S, bn, K.XBAR_ROWS) if transpose else (S, K.XBAR_ROWS, bn)
    w = jnp.asarray(rng.integers(-128, 128, size=wshape), jnp.int8)
    xq = jnp.asarray(rng.integers(-(2**15 - 1), 2**15, size=(bb, K.XBAR_ROWS)), jnp.int32)
    kw = dict(tile_idx=jnp.int32(3), col0=jnp.int32(2 * bn)) if noisy else {}
    got = K._tile_compute(xq, w, spec=spec, io_bits=16, adc_bits=adc_bits,
                          transpose=transpose, dev=dev, **kw)
    want = _float_tile(xq, w, spec, 16, adc_bits, transpose, dev, kw.get("tile_idx"), kw.get("col0"))
    np.testing.assert_array_equal(_bits(got), _bits(want))


@pytest.mark.parametrize("adc_bits", [1, 9, 16])
@pytest.mark.parametrize("transpose", [False, True], ids=["fwd", "mtvm"])
def test_finite_adc_tile_has_no_division(adc_bits, transpose):
    """The code-domain ADC multiplies by a per-slice 1/step: the tile body's
    jaxpr holds no division, and still one MXU dot."""
    prims = K.tile_primitives(DEFAULT_SPEC, 16, adc_bits, transpose=transpose)
    assert prims["div"] == 0, prims
    assert prims["dot_general"] == 1, prims


@pytest.mark.parametrize("spec", ADC_SPECS, ids=lambda s: s.name())
def test_adc_steps_are_powers_of_two(spec):
    for adc_bits in range(1, 17):
        for st, pm in zip(K.adc_steps(spec, adc_bits), spec.plane_max):
            assert st == 2.0 * K.XBAR_ROWS * pm / 2**adc_bits
            assert np.frexp(st)[0] == 0.5
