"""Public entry points for sliced-OPA.

Dispatch policy (``use_kernel=None`` → auto): the Mosaic kernel engages on
TPU; on CPU (this container, and the 512-device dry-run host) the pure-jnp
reference path is used — it is value-equivalent (tested) and produces clean
SPMD-shardable HLO. Tests force ``use_kernel=True, interpret=True`` to
execute the kernel body on CPU.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.slicing import SliceSpec
from . import kernel as _k
from . import ref as _ref


def _resolve(use_kernel: bool | None, interpret: bool | None) -> tuple[bool, bool]:
    on_tpu = jax.default_backend() == "tpu"
    if use_kernel is None:
        use_kernel = on_tpu
    if interpret is None:
        interpret = not on_tpu
    return use_kernel, interpret


def opa_deposit(planes, p_q, spec: SliceSpec, *, use_kernel: bool | None = None, interpret: bool | None = None):
    """Saturating digit deposit of an int32 update into int8 planes [S, *w].

    Accepts any parameter rank >= 2 (e.g. scan-stacked [S, L, M, N]);
    leading dims are flattened for the rank-3 kernel.
    """
    use_kernel, interpret = _resolve(use_kernel, interpret)
    if not use_kernel:
        return _ref.opa_deposit_ref(planes, p_q, spec)
    shape = planes.shape
    if planes.ndim > 3:
        m = 1
        for d in shape[1:-1]:
            m *= d
        planes3 = planes.reshape(shape[0], m, shape[-1])
        out = _k.opa_deposit(planes3, p_q.reshape(m, shape[-1]), spec=spec, interpret=interpret)
        return out.reshape(shape)
    return _k.opa_deposit(planes, p_q, spec=spec, interpret=interpret)


def _normalize_device(device):
    """None unless some write-path field is non-ideal (an all-ideal
    DeviceModel must compile the exact ideal kernel)."""
    if device is None or not device.writes_nonideal():
        return None
    return device


def opa_device_update(planes, g, lr, frac_bits, spec: SliceSpec, *, device,
                      stochastic: bool = False, key=None, rng_mode: str = "counter",
                      use_kernel: bool | None = None, interpret: bool | None = None):
    """Dense-gradient crossbar update under a write-nonideal ``DeviceModel``:
    the same physics pipeline as the operand path's ``opa_fused_update``
    (asymmetry -> write noise -> rounding -> deposit -> stuck mask), applied
    to an already-materialized ``[*stack, M, N]`` gradient — so a plan leaf
    whose gradient is dense (embeddings, momentum/Tiki-Taka buffers) writes
    through the identical device model. ``device`` must already be
    write-nonideal (callers branch on ``writes_nonideal()``; the ideal path
    is the verbatim quantize + ``opa_deposit`` composition)."""
    from repro.core.fixed_point import exp2i

    if device.write_noise > 0.0 and key is None:
        raise ValueError("DeviceModel.write_noise requires a PRNG key")
    y = g.astype(jnp.float32) * -jnp.asarray(lr, jnp.float32) * exp2i(frac_bits)
    upd = _ref.write_device(y, device,
                            key=key, stochastic=stochastic, rng_mode=rng_mode)
    new = opa_deposit(planes, upd, spec, use_kernel=use_kernel, interpret=interpret)
    if device.stuck_frac > 0.0:
        new = jnp.where(_ref.stuck_mask_ref(device, spec, planes.shape), planes, new)
    return new


def opa_fused(planes, x, dh, scale, spec: SliceSpec, *, use_kernel: bool | None = None,
              interpret: bool | None = None, device=None, dkey=None):
    """Fused X^T@dH -> quantize -> deposit (gradient never hits HBM).

    ``device``/``dkey`` expose the write-path ``DeviceModel`` on the raw
    entry (``dkey`` int32 [2] key words when ``device.write_noise > 0``)."""
    use_kernel, interpret = _resolve(use_kernel, interpret)
    device = _normalize_device(device)
    if not use_kernel:
        return _ref.opa_fused_ref(planes, x, dh, scale, spec, device=device, dkey=dkey)
    return _k.opa_fused(planes, x, dh, scale, spec=spec, interpret=interpret,
                        dev=device, dkey=dkey)


def opa_fused_update(
    planes,
    x,
    dh,
    lr,
    frac_bits,
    spec: SliceSpec,
    *,
    stochastic: bool = False,
    key=None,
    rng_mode: str = "counter",
    use_kernel: bool | None = None,
    interpret: bool | None = None,
    device=None,
):
    """The full PANTHER weight update from gradient *operands*.

    Semantically ``opa_deposit(planes, quantize(-lr * x^T@dh, frac_bits,
    stochastic, key, rng_mode))`` — but on the kernel path the ``[M, N]``
    gradient is formed tile-by-tile in VMEM and deposited in the same pass,
    never reaching HBM. ``-lr`` and the ``2**F`` weight grid fold into the
    kernel's scalar scale.

    ``rng_mode`` selects the stochastic-rounding noise source:

    * ``"counter"`` (default) — the stateless coordinate hash. The kernel
      generates the draw in VMEM from two prefetched key words; the jnp
      reference (and the dense pipeline's ``quantize``) computes the same
      bits, so all paths stay bit-compatible and nothing noise-shaped
      crosses HBM.
    * ``"grid"`` — legacy ``jax.random.uniform`` grid fed to the kernel as
      an ``[M, N]`` HBM input: the PR 1-5 draw, kept (golden-tested) so old
      checkpoints replay bit-identically.
    * ``"hw"`` — the TPU hardware PRNG inside the kernel. Fastest on real
      hardware; not bit-reproducible against the CPU reference (and
      unavailable off-TPU), so it requires the kernel dispatch.

    Shapes: planes int8 ``[S, *stack, M, N]``; x ``[*stack, T, M]``;
    dh ``[*stack, T, N]``. Stacked (lax.scan layer-group) leaves run the
    kernel per layer under a lax.scan; layer ``l`` derives its key as
    ``fold_in(key, l)`` — the same per-layer derivation
    ``core.fixed_point.counter_uniform`` applies on the dense path, so both
    pipelines consume identical noise for a given leaf key.

    ``device`` (a ``models.common.DeviceModel``) turns on the write-path
    non-idealities at the deposit — see ``kernel.opa_fused``. The write-noise
    key stream is ``fold_in(key, WRITE_NOISE_FOLD)`` (independent of the
    rounding stream; fig9 runs deterministic rounding, so it cannot
    piggyback), with the same per-layer ``fold_in(·, l)`` derivation for
    stacked leaves on both the kernel and reference paths.
    """
    use_kernel, interpret = _resolve(use_kernel, interpret)
    if stochastic and key is None:
        raise ValueError("stochastic rounding requires a PRNG key")
    device = _normalize_device(device)
    if device is not None and device.write_noise > 0.0 and key is None:
        raise ValueError("DeviceModel.write_noise requires a PRNG key")
    if not use_kernel:
        if stochastic and rng_mode == "hw":
            raise ValueError(
                "rng_mode='hw' uses the TPU hardware PRNG and has no reference "
                "path; use 'counter' (reproducible) off-TPU"
            )
        return _ref.opa_fused_update_ref(
            planes, x, dh, lr, frac_bits, spec,
            stochastic=stochastic, key=key, rng_mode=rng_mode, device=device,
        )

    # exp2i: the 2^F grid scale must be the exact power of two the dense
    # pipeline's quantize() uses, or the fused/dense bit-compat breaks
    from repro.core.fixed_point import WRITE_NOISE_FOLD, counter_key_scalars, exp2i

    scale, grid = -jnp.asarray(lr, jnp.float32), exp2i(frac_bits)
    noise = rkey = None
    if stochastic and rng_mode == "grid":
        noise = jax.random.uniform(key, planes.shape[1:], jnp.float32)
    elif stochastic:
        rkey = counter_key_scalars(key)
    dk_base = None
    if device is not None and device.write_noise > 0.0:
        dk_base = jax.random.fold_in(key, WRITE_NOISE_FOLD)
    rng_impl = rng_mode if stochastic else "counter"

    if planes.ndim == 3:
        return _k.opa_fused(
            planes, x, dh, scale, spec=spec, interpret=interpret,
            noise=noise, rkey=rkey, rng_impl=rng_impl, dev=device,
            dkey=None if dk_base is None else counter_key_scalars(dk_base),
            grid_scale=grid,
        )

    # stacked leaf [S, *stack, M, N]: one kernel launch per stacked layer
    S = planes.shape[0]
    M, N = planes.shape[-2:]
    L = 1
    for d in planes.shape[1:-2]:
        L *= d
    T = x.shape[-2]
    xs = {
        "p": jnp.moveaxis(planes.reshape(S, L, M, N), 1, 0),  # [L, S, M, N]
        "x": x.reshape(L, T, M),
        "dh": dh.reshape(L, T, N),
    }
    if noise is not None:
        xs["n"] = noise.reshape(L, M, N)
    elif rkey is not None:
        # per-layer key words [L, 2]: fold_in(key, l), as on the dense path
        xs["k"] = jax.vmap(
            lambda l: counter_key_scalars(jax.random.fold_in(key, l))
        )(jnp.arange(L))
    if dk_base is not None:
        # write-noise stream, same per-layer derivation (counter_gauss_array)
        xs["dk"] = jax.vmap(
            lambda l: counter_key_scalars(jax.random.fold_in(dk_base, l))
        )(jnp.arange(L))

    def body(_, a):
        return None, _k.opa_fused(
            a["p"], a["x"], a["dh"], scale, spec=spec, interpret=interpret,
            noise=a.get("n"), rkey=a.get("k"), rng_impl=rng_impl,
            dev=device, dkey=a.get("dk"), grid_scale=grid,
        )

    _, out = jax.lax.scan(body, None, xs)
    return jnp.moveaxis(out, 0, 1).reshape(planes.shape)
