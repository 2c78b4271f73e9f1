"""DeviceModel: non-ideal ReRAM physics at the OPA deposit and the MVM read.

Contracts under test:

* ``device=None`` (and an all-ideal ``DeviceModel()``) is BIT-identical to
  the ideal path at every injection site — array_equal, kernel and ref;
* device-on OPA kernel == OPA ref bit-for-bit (integer deposit pipeline);
  device-on MVM kernel vs ref is allclose (the noise add breaks the exact
  integer reassociation the None path enjoys, same class as finite-ADC);
* write noise is deterministic in the key, asymmetry scales up/down
  increments, stuck cells freeze, read noise is a static pattern with
  global (tile, column) coordinates that survive sharding;
* the per-leaf plan threads a DeviceModel end to end through
  ``make_train_step``.
"""
from __future__ import annotations

import dataclasses
import os
import subprocess
import sys
import textwrap

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import DEFAULT_SPEC, slice_weights
from repro.core.fixed_point import choose_frac_bits
from repro.kernels.sliced_mvm import ops as MO
from repro.kernels.sliced_mvm import ref as MR
from repro.kernels.sliced_opa import opa_deposit, opa_device_update, opa_fused_update
from repro.kernels.sliced_opa import ref as OR
from repro.models.common import DeviceModel, FidelityConfig
from repro.optim import PantherConfig
from repro.optim.schedules import constant
from repro.plan import default_rules
from repro.train.step import make_train_step, train_state_init

SPEC = DEFAULT_SPEC
IO_BITS = 16
DEV = DeviceModel(write_noise=0.5, asym_up=1.2, asym_down=0.8, stuck_frac=0.05,
                  stuck_seed=3, read_noise=0.01)
KEY = jax.random.PRNGKey(42)


def _opa_case(m=256, n=192, t=32, seed=0):
    rng = np.random.default_rng(seed)
    planes = jnp.asarray(rng.integers(-7, 8, size=(SPEC.n_slices, m, n)), jnp.int8)
    x = jnp.asarray(rng.normal(size=(t, m)), jnp.float32)
    dh = jnp.asarray(rng.normal(size=(t, n)), jnp.float32)
    return planes, x, dh


def _mvm_case(m=256, n=192, b=16, seed=0):
    rng = np.random.default_rng(seed)
    planes = jnp.asarray(rng.integers(-7, 8, size=(SPEC.n_slices, m, n)), jnp.int8)
    x = jnp.asarray(rng.normal(size=(b, m)), jnp.float32)
    xt = jnp.asarray(rng.normal(size=(b, n)), jnp.float32)
    return planes, x, xt


# ------------------------- None / all-ideal bit-identity --------------------


@pytest.mark.parametrize("use_kernel", [False, True])
def test_opa_none_and_ideal_device_bit_identical(use_kernel):
    planes, x, dh = _opa_case()
    kw = dict(stochastic=False, use_kernel=use_kernel, interpret=use_kernel)
    base = opa_fused_update(planes, x, dh, 0.1, jnp.int32(12), SPEC, **kw)
    for dev in (None, DeviceModel()):
        got = opa_fused_update(planes, x, dh, 0.1, jnp.int32(12), SPEC,
                               device=dev, key=KEY, **kw)
        assert jnp.array_equal(got, base)


@pytest.mark.parametrize("use_kernel", [False, True])
@pytest.mark.parametrize("transpose", [False, True])
@pytest.mark.parametrize("adc_bits", [None, 9])
def test_mvm_none_ideal_and_writeonly_device_bit_identical(use_kernel, transpose, adc_bits):
    """Reads only engage on read_noise > 0: None, all-ideal, and a
    write-noise-only device all compile the exact ideal read."""
    planes, x, xt = _mvm_case()
    xin = xt if transpose else x
    fb = choose_frac_bits(xin, word_bits=IO_BITS, margin_bits=2, clip_to_word=False)
    kw = dict(io_bits=IO_BITS, adc_bits=adc_bits, transpose=transpose,
              use_kernel=use_kernel, interpret=use_kernel)
    base = MO.mvm_sliced_fused(planes, xin, fb, SPEC, **kw)
    for dev in (None, DeviceModel(), DeviceModel(write_noise=0.5, asym_up=1.3)):
        got = MO.mvm_sliced_fused(planes, xin, fb, SPEC, device=dev, **kw)
        assert jnp.array_equal(got, base), (dev, transpose, adc_bits)


# ------------------------------ kernel vs ref -------------------------------


def test_opa_device_kernel_bit_identical_to_ref():
    planes, x, dh = _opa_case()
    a = opa_fused_update(planes, x, dh, 0.1, jnp.int32(12), SPEC, device=DEV,
                         key=KEY, use_kernel=False)
    b = opa_fused_update(planes, x, dh, 0.1, jnp.int32(12), SPEC, device=DEV,
                         key=KEY, use_kernel=True, interpret=True)
    assert jnp.array_equal(a, b)
    assert not jnp.array_equal(a, opa_fused_update(
        planes, x, dh, 0.1, jnp.int32(12), SPEC, use_kernel=False))


@pytest.mark.parametrize("transpose", [False, True])
@pytest.mark.parametrize("adc_bits", [None, 9])
def test_mvm_device_kernel_close_to_ref(transpose, adc_bits):
    """Device-on reads: the noise-offset add breaks the ideal path's exact
    integer fold reassociation, so kernel-vs-ref is allclose (measured
    up to ~1e-5 rel at ideal ADC, ~2.3e-7 at finite — the finite class the
    pre-existing ideal-vs-kernel gap already occupies), not array_equal."""
    dev = DeviceModel(read_noise=0.01)
    planes, x, xt = _mvm_case()
    xin = xt if transpose else x
    fb = choose_frac_bits(xin, word_bits=IO_BITS, margin_bits=2, clip_to_word=False)
    kw = dict(io_bits=IO_BITS, adc_bits=adc_bits, transpose=transpose, device=dev)
    a = MO.mvm_sliced_fused(planes, xin, fb, SPEC, use_kernel=False, **kw)
    b = MO.mvm_sliced_fused(planes, xin, fb, SPEC, use_kernel=True, interpret=True, **kw)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4)
    # and the noise actually moved the output
    ideal = MO.mvm_sliced_fused(planes, xin, fb, SPEC, use_kernel=False,
                                io_bits=IO_BITS, adc_bits=adc_bits, transpose=transpose)
    assert not jnp.array_equal(a, ideal)


def test_mvm_double_buffer_matches_3d_grid_with_device():
    dev = DeviceModel(read_noise=0.02)
    planes, x, _ = _mvm_case()
    fb = choose_frac_bits(x, word_bits=IO_BITS, margin_bits=2, clip_to_word=False)
    kw = dict(io_bits=IO_BITS, adc_bits=9, device=dev, use_kernel=True, interpret=True)
    a = MO.mvm_sliced_fused(planes, x, fb, SPEC, double_buffer=False, **kw)
    b = MO.mvm_sliced_fused(planes, x, fb, SPEC, double_buffer=True, **kw)
    assert jnp.array_equal(a, b)


# ----------------------------- write-path physics ---------------------------


def test_write_asymmetry_scales_increments():
    dev = DeviceModel(asym_up=1.5, asym_down=0.5)
    y = jnp.asarray([[2.0, -2.0, 4.0, -4.0]], jnp.float32)
    got = OR.write_device(y, dev, key=None, stochastic=False, rng_mode="counter")
    assert got.tolist() == [[3, -1, 6, -2]]


def test_write_noise_deterministic_in_key():
    planes, x, dh = _opa_case()
    dev = DeviceModel(write_noise=1.0)
    args = (planes, x, dh, 0.1, jnp.int32(12), SPEC)
    a = opa_fused_update(*args, device=dev, key=KEY)
    b = opa_fused_update(*args, device=dev, key=KEY)
    c = opa_fused_update(*args, device=dev, key=jax.random.PRNGKey(7))
    assert jnp.array_equal(a, b)
    assert not jnp.array_equal(a, c)
    with pytest.raises(ValueError, match="requires a PRNG key"):
        opa_fused_update(*args, device=dev)


def test_stuck_cells_freeze_updates():
    planes, x, dh = _opa_case()
    all_stuck = DeviceModel(stuck_frac=1.0)
    got = opa_fused_update(planes, x, dh, 0.1, jnp.int32(12), SPEC, device=all_stuck)
    assert jnp.array_equal(got, planes)
    # partial mask: static in the seed, different across seeds
    m3 = OR.stuck_mask_ref(DeviceModel(stuck_frac=0.3, stuck_seed=3), SPEC, planes.shape)
    assert jnp.array_equal(
        m3, OR.stuck_mask_ref(DeviceModel(stuck_frac=0.3, stuck_seed=3), SPEC, planes.shape))
    m4 = OR.stuck_mask_ref(DeviceModel(stuck_frac=0.3, stuck_seed=4), SPEC, planes.shape)
    assert not jnp.array_equal(m3, m4)
    frac = float(jnp.mean(m3.astype(jnp.float32)))
    assert 0.25 < frac < 0.35
    # stuck cells keep their pre-update value through the fused update
    part = DeviceModel(stuck_frac=0.3, stuck_seed=3)
    got = opa_fused_update(planes, x, dh, 0.1, jnp.int32(12), SPEC, device=part)
    assert jnp.array_equal(jnp.where(m3, got, 0), jnp.where(m3, planes, 0))


def test_dense_device_update_matches_write_device_composition():
    """opa_device_update (the dense-gradient / momentum-buffer path) is the
    write_device -> opa_deposit -> stuck-freeze composition, exactly."""
    planes, _, _ = _opa_case()
    rng = np.random.default_rng(5)
    g = jnp.asarray(rng.normal(size=planes.shape[1:]), jnp.float32)
    dev = DeviceModel(write_noise=0.5, asym_up=1.2, asym_down=0.8,
                      stuck_frac=0.2, stuck_seed=9)
    got = opa_device_update(planes, g, 0.1, jnp.int32(12), SPEC, device=dev, key=KEY)
    upd = OR.write_device(g * (-0.1 * float(2**12)), dev, key=KEY,
                          stochastic=False, rng_mode="counter")
    want = opa_deposit(planes, upd, SPEC)
    mask = OR.stuck_mask_ref(dev, SPEC, planes.shape)
    want = jnp.where(mask, planes, want)
    assert jnp.array_equal(got, want)


# ------------------------------ read-path physics ---------------------------


def test_read_noise_static_pattern_and_salted_transpose():
    dev = DeviceModel(read_noise=0.02)
    offs = MR.read_offsets_ref(dev, SPEC, jnp.int32(0), jnp.int32(0), 64, False)
    again = MR.read_offsets_ref(dev, SPEC, jnp.int32(0), jnp.int32(0), 64, False)
    assert jnp.array_equal(offs, again)  # frozen pattern: no RNG state
    # transpose reads go through a different ADC bank: different salt
    offt = MR.read_offsets_ref(dev, SPEC, jnp.int32(0), jnp.int32(0), 64, True)
    assert not jnp.array_equal(offs, offt)
    # different crossbar tiles see different offsets
    off1 = MR.read_offsets_ref(dev, SPEC, jnp.int32(1), jnp.int32(0), 64, False)
    assert not jnp.array_equal(offs, off1)
    # sigma scales the per-slice full-scale linearly
    off2 = MR.read_offsets_ref(DeviceModel(read_noise=0.04), SPEC,
                               jnp.int32(0), jnp.int32(0), 64, False)
    np.testing.assert_allclose(np.asarray(off2), 2 * np.asarray(offs), rtol=1e-6)


def test_sharded_device_read_matches_single_host():
    """The global (tile, column) offset coordinates survive the shard_map
    lowering: a read-noisy MVM/MᵀVM sharded over contraction or output dims
    reproduces the single-host fused read (reassociation-close)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = "src"
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent("""
            import numpy as np, jax, jax.numpy as jnp
            from repro.core import DEFAULT_SPEC, slice_weights
            from repro.core.fixed_point import choose_frac_bits
            from repro.kernels.sliced_mvm import mvm_sliced_fused_batched, mvm_sliced_sharded
            from repro.launch.mesh import make_mesh
            from repro.models.common import DeviceModel
            dev = DeviceModel(read_noise=0.02)
            rng = np.random.default_rng(0)
            M = N = 512  # 4-way model shards hold exactly one 128-row tile each
            q = jnp.asarray(rng.integers(-256, 257, size=(M, N)), jnp.int32)
            planes = slice_weights(q, DEFAULT_SPEC)
            mesh = make_mesh((2, 4), ("data", "model"))
            for transpose in (False, True):
                contract = N if transpose else M
                x = jnp.asarray(rng.normal(size=(3, 5, contract)), jnp.float32)
                fb = choose_frac_bits(x, word_bits=16, margin_bits=2, clip_to_word=False)
                for adc in (None, 9):
                    ref = np.asarray(mvm_sliced_fused_batched(
                        planes, x, fb, DEFAULT_SPEC, adc_bits=adc,
                        transpose=transpose, device=dev))
                    ideal = np.asarray(mvm_sliced_fused_batched(
                        planes, x, fb, DEFAULT_SPEC, adc_bits=adc, transpose=transpose))
                    assert (ref != ideal).any(), (transpose, adc)
                    for sd in (None, 0, 1):
                        got = np.asarray(jax.jit(lambda xx: mvm_sliced_sharded(
                            planes, xx, DEFAULT_SPEC, mesh=mesh, data_axes=("data",),
                            model_axis="model", shard_dim=sd, adc_bits=adc,
                            transpose=transpose, frac_bits=fb, device=dev))(x))
                        np.testing.assert_allclose(got, ref, rtol=1e-4,
                                                   err_msg=str((transpose, adc, sd)))
            print("DEVICE_SHARD_OK")
        """)],
        capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert "DEVICE_SHARD_OK" in out.stdout


# ------------------------------- end to end ---------------------------------


def _smoke_setup():
    from repro.configs import get_smoke

    cfg = dataclasses.replace(get_smoke("gemma_2b"), dtype=jnp.float32)
    opt = PantherConfig(stochastic_round=False, crs_every=1000)
    batch = {
        "inputs": jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, cfg.vocab),
        "labels": jax.random.randint(jax.random.PRNGKey(2), (4, 16), 0, cfg.vocab),
    }
    return cfg, opt, batch


def test_train_step_threads_device_plan():
    """A plan-carried DeviceModel reaches the fused deposit: the noisy run's
    planes diverge from ideal, while an all-ideal DeviceModel() plan stays
    bit-identical to the no-device plan (the anchor the CI gate watches)."""
    cfg, opt, batch = _smoke_setup()

    def run(device):
        fid = FidelityConfig(adc_bits_fwd=9, adc_bits_bwd=9, device=device)
        s0 = train_state_init(cfg, opt, jax.random.PRNGKey(0))
        step = jax.jit(make_train_step(cfg, opt, constant(0.3),
                                       plan_rules=default_rules(opt, fidelity=fid)))
        s1, m = step(s0, batch)
        return s1, m

    s_none, m_none = run(None)
    s_ideal, m_ideal = run(DeviceModel())
    assert float(m_none["loss"]) == float(m_ideal["loss"])
    for a, b in zip(jax.tree.leaves(s_none.sliced), jax.tree.leaves(s_ideal.sliced)):
        assert (np.asarray(a) == np.asarray(b)).all()

    s_dev, m_dev = run(DeviceModel(write_noise=2.0, asym_up=1.2, asym_down=0.8,
                                   stuck_frac=0.02, read_noise=0.01))
    assert np.isfinite(float(m_dev["loss"]))
    assert any(
        (np.asarray(a.planes) != np.asarray(b.planes)).any()
        for a, b in zip(
            jax.tree.leaves(s_none.sliced, is_leaf=lambda x: hasattr(x, "planes")),
            jax.tree.leaves(s_dev.sliced, is_leaf=lambda x: hasattr(x, "planes")),
        )
        if hasattr(a, "planes")
    )
