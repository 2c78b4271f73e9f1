"""``panther_mvm_fused*``: the bit-sliced crossbar read (forward MVM, and
``_t`` the transposed MᵀVM). Operations are the column-current MACs,
2*T*M*N*(io_bits-1)*S, against the int8 peak since every operand fits 8
bits; bytes the planes read plus the input and the output."""
from bench.work import matrices, nbytes, planes

IO_BITS = 16  # the DAC width of every read (bench/refmodel.py, Numerics)
FAMILIES = ("panther_mvm_fused", "panther_mvm_fused_db", "panther_mvm_fused_t", "panther_mvm_fused_t_db")


def work(call):
    p = planes(call)
    S, M, N = p[1]
    (x,) = matrices(call)
    (out,) = call["result"]
    ops = 2 * x[1][0] * M * N * (IO_BITS - 1) * S
    return ops, nbytes(p) + nbytes(x) + nbytes(out), "int8_ops_per_s"
