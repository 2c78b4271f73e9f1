"""``panther_opa_deposit``: a dense int32 update split into digits and added
to the planes. No matrix operations; bytes the planes read and written plus
the update read once."""
from bench.work import nbytes, planes

FAMILIES = ("panther_opa_deposit",)


def work(call):
    p = planes(call)
    (upd,) = [o for o in call["operands"] if o[0] == "s32" and len(o[1]) == 2]
    return 0, 2 * nbytes(p) + nbytes(upd), "bf16_flops_per_s"
