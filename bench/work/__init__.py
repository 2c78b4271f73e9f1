"""Operations and bytes one call of a kernel family needs, from the call's
operand and result shapes as the compiled program gives them. Each module
has ``FAMILIES`` (the kernel names it counts) and ``work(call) ->
(ops, bytes, peak key of bench/peaks.json)``."""

DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
               "s32": 4, "u32": 4, "f32": 4, "f8e4m3fn": 1, "f8e5m2": 1}


def nbytes(operand) -> int:
    dtype, shape = operand
    n = DTYPE_BYTES[dtype]
    for d in shape:
        n *= d
    return n


def planes(call):
    """The int8 digit planes [S, M, N] of a call."""
    (p,) = [o for o in call["operands"] if o[0] == "s8" and len(o[1]) == 3]
    return p


def matrices(call) -> list:
    """The float rank-2 operands with more than one row (activations and
    cotangents; the [1, k] SMEM scalars are left out)."""
    return [o for o in call["operands"]
            if o[0] in ("f32", "bf16") and len(o[1]) == 2 and o[1][0] > 1]
