"""The per-call work counts of bench/work/*, each against a hand count."""
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from bench.work import mvm_fused, opa_deposit, opa_fused  # noqa: E402


def test_opa_fused_work():
    # x [T=512, M=256] and dh [T, N=512] as the kernel receives them (f32)
    call = {"operands": [("f32", (1, 2)), ("f32", (512, 256)), ("f32", (512, 512)),
                         ("s8", (8, 256, 512)), ("s32", (1, 2))],
            "result": [("s8", (8, 256, 512))]}
    ops, nbytes, peak = opa_fused.work(call)
    assert ops == 2 * 512 * 256 * 512 == 134_217_728
    # planes read and written, x and dh read once
    assert nbytes == 2 * 1_048_576 + 524_288 + 1_048_576 == 3_670_016
    assert peak == "bf16_flops_per_s"


def test_opa_deposit_work():
    call = {"operands": [("s32", (256, 512)), ("s8", (8, 256, 512))],
            "result": [("s8", (8, 256, 512))]}
    ops, nbytes, peak = opa_deposit.work(call)
    assert ops == 0
    assert nbytes == 2 * 1_048_576 + 524_288 == 2_621_440


def test_mvm_fused_work_forward_and_transposed():
    fwd = {"operands": [("f32", (1, 1)), ("f32", (64, 256)), ("s8", (8, 256, 512))],
           "result": [("f32", (64, 512))]}
    ops, nbytes, peak = mvm_fused.work(fwd)
    # 15 magnitude bits x 8 slices of column currents per weight MAC
    assert ops == 2 * 64 * 256 * 512 * 15 * 8 == 2_013_265_920
    assert nbytes == 1_048_576 + 65_536 + 131_072
    assert peak == "int8_ops_per_s"
    bwd = {"operands": [("f32", (1, 1)), ("f32", (64, 512)), ("s8", (8, 256, 512))],
           "result": [("f32", (64, 256))]}
    assert mvm_fused.work(bwd) == (ops, nbytes, peak)
