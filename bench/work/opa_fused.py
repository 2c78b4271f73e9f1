"""``panther_opa_fused``: X^T dH on the MXU, quantized and deposited into the
planes without the gradient reaching HBM. Operations 2*T*M*N against the
bf16 peak; bytes the planes read and written plus x [T, M] and dh [T, N]
read once."""
from bench.work import matrices, nbytes, planes

FAMILIES = ("panther_opa_fused",)


def work(call):
    p = planes(call)
    S, M, N = p[1]
    x, dh = matrices(call)
    T = x[1][0]
    return 2 * T * M * N, 2 * nbytes(p) + nbytes(x) + nbytes(dh), "bf16_flops_per_s"
