"""Public entry point for the CRS kernel."""
from __future__ import annotations

import jax

from repro.core.slicing import SliceSpec
from . import kernel as _k
from . import ref as _ref


def crs(planes, spec: SliceSpec, *, use_kernel: bool | None = None, interpret: bool | None = None):
    """Carry resolution of int8 planes ``[S, *w]`` (auto: Pallas on TPU, jnp
    ref elsewhere). CRS is elementwise per cell, so scan-stacked planes
    ``[S, L, M, N]`` flatten their leading dims into rows for the rank-3
    kernel, as ``sliced_opa.opa_deposit`` does."""
    on_tpu = jax.default_backend() == "tpu"
    if use_kernel is None:
        use_kernel = on_tpu
    if interpret is None:
        interpret = not on_tpu
    if not use_kernel:
        return _ref.crs_ref(planes, spec)
    shape = planes.shape
    planes3 = planes.reshape(shape[0], -1, shape[-1])
    return _k.crs(planes3, spec=spec, interpret=interpret).reshape(shape)
