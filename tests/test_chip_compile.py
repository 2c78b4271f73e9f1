"""Compile the main-path Pallas kernels for a described TPU v5e chip at
phi4-mini widths (d_model 3072, d_ff 8192, vocab 200,064).

Nothing runs: each test lowers one kernel with abstract shapes placed on a
described (not attached) chip and asserts that Mosaic accepted it — the
compiled program holds the kernel as a ``tpu_custom_call``. This catches what
interpret mode cannot (unsupported primitives, unaligned slices, VMEM
budgets). The topology is described inside a module fixture, never at import:
only the worker that runs this file loads the TPU compiler.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest

from repro.core.slicing import DEFAULT_SPEC
from repro.kernels.common import tpu_kernels_in_hlo
from repro.kernels.crs import kernel as crs_k
from repro.kernels.sliced_mvm import kernel as mvm_k
from repro.kernels.sliced_opa import kernel as opa_k

S = DEFAULT_SPEC.n_slices
D_MODEL, D_FF, VOCAB = 3072, 8192, 200064  # phi4-mini published widths
TOKENS = 512


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this environment
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


def _assert_kernel(compiled, name):
    kernels = tpu_kernels_in_hlo(compiled.as_text())
    assert kernels.get(name, 0) >= 1, kernels


def test_opa_fused_counter_rng_compiles(one_chip):
    c = _compile(
        lambda p, x, dh, sc, k: opa_k.opa_fused(
            p, x, dh, sc, spec=DEFAULT_SPEC, rkey=k, rng_impl="counter"),
        one_chip,
        ((S, D_MODEL, D_FF), jnp.int8), ((TOKENS, D_MODEL), jnp.float32),
        ((TOKENS, D_FF), jnp.float32), ((), jnp.float32), ((2,), jnp.int32),
    )
    _assert_kernel(c, "panther_opa_fused")


def test_opa_deposit_vocab_rows_compiles(one_chip):
    c = _compile(
        lambda p, q: opa_k.opa_deposit(p, q, spec=DEFAULT_SPEC),
        one_chip, ((S, VOCAB, D_MODEL), jnp.int8), ((VOCAB, D_MODEL), jnp.int32),
    )
    _assert_kernel(c, "panther_opa_deposit")


def test_crs_compiles(one_chip):
    # scan-stacked [S, L, M, N] planes reach the kernel flattened to rows
    c = _compile(lambda p: crs_k.crs(p, spec=DEFAULT_SPEC),
                 one_chip, ((S, 2 * D_MODEL, D_FF), jnp.int8))
    _assert_kernel(c, "panther_crs")


@pytest.mark.parametrize("double_buffer", [True, False], ids=["db", "grid3d"])
@pytest.mark.parametrize("transpose", [False, True], ids=["mvm", "mtvm"])
@pytest.mark.parametrize("adc_bits", [9, None], ids=["adc9", "ideal"])
def test_mvm_sliced_fused_compiles(one_chip, adc_bits, transpose, double_buffer):
    contract = D_FF if transpose else D_MODEL
    c = _compile(
        lambda p, x, f: mvm_k.mvm_sliced_fused(
            p, x, f, spec=DEFAULT_SPEC, adc_bits=adc_bits, transpose=transpose,
            double_buffer=double_buffer),
        one_chip,
        ((S, D_MODEL, D_FF), jnp.int8), ((256, contract), jnp.float32), ((), jnp.int32),
    )
    name = "panther_mvm_fused" + ("_t" if transpose else "") + ("_db" if double_buffer else "")
    _assert_kernel(c, name)


PHI4_READS = {  # [M, N] of the five projections a phi4-mini layer reads
    "wqkv": (D_MODEL, 5120), "attn_wo": (D_MODEL, D_MODEL), "wi_gate": (D_MODEL, D_FF),
    "wi_up": (D_MODEL, D_FF), "mlp_wo": (D_FF, D_MODEL),
}


@pytest.mark.parametrize("transpose", [False, True], ids=["mvm", "mtvm"])
@pytest.mark.parametrize("read", sorted(PHI4_READS))
def test_mvm_fused_token_blocks_compile(one_chip, read, transpose):
    """A 512-token adc9 step's reads in their chosen 128-row token blocks:
    Mosaic accepts each under the kernel's scoped-VMEM limit."""
    m, n = PHI4_READS[read]
    contract, out_dim = (n, m) if transpose else (m, n)
    bb = mvm_k.pick_token_block(TOKENS, mvm_k.DEFAULT_BN, contract, DEFAULT_SPEC, 16, 9)
    assert bb == 128
    assert mvm_k.read_vmem_bytes(bb, mvm_k.DEFAULT_BN, contract, DEFAULT_SPEC, 16, 9) \
        <= mvm_k.VMEM_LIMIT
    c = _compile(
        lambda p, x, f: mvm_k.mvm_sliced_fused(
            p, x, f, spec=DEFAULT_SPEC, adc_bits=9, transpose=transpose),
        one_chip,
        ((S, m, n), jnp.int8), ((TOKENS, contract), jnp.float32), ((), jnp.int32),
    )
    _assert_kernel(c, "panther_mvm_fused" + ("_t" if transpose else "") + "_db")
