"""PANTHER sliced-SGD: the paper's technique as a first-class JAX optimizer.

Every crossbar-mapped parameter lives as int8 digit planes ``[S, *shape]``
plus a per-tensor fixed-point scale. The update is the paper's OPA: quantize
``-lr * grad`` onto the weight grid (stochastic rounding) and deposit it
into the planes with per-plane saturating carry accumulation. A Carry
Resolution Step re-canonicalizes every ``crs_every`` steps (paper default
1024). Vector parameters (norm scales, biases, SSM ``A_log``/dt) take the
paper's digital-VFU path: plain float SGD.

Gradients arrive in one of two forms per leaf. *Dense* leaves carry the
materialized ``[M, N]`` gradient (quantize + ``opa_deposit``). *Operand*
leaves carry an :class:`~repro.models.common.OperandGroup` — the activation
/ cotangent factor pair of the outer product — and go through
``opa_fused_update``: the dense gradient never exists in HBM, exactly the
paper's in-crossbar OPA. The operand contract is no longer matmul-only;
``OperandGroup.kind`` selects the layout:

``"matmul"``
    ``x [*stack, T, M]``, ``dh [*stack, T, N]`` — linear layers, and MoE
    expert banks whose expert axis rides the leading stack (the grouped
    einsum's per-expert token buffers are the operands).
``"im2col"``
    ``x [*stack, C, T, K]``, ``dh [*stack, C, T, 1]`` — depthwise-conv taps
    stored as ``[K, C]`` tiles. The deposit runs on a channel-as-stack
    transposed view of the planes (``[S, ..., C, K, 1]``), an elementwise
    bijection, then transposes back; CRS always applies on the stored
    ``[S, ..., K, C]`` layout.

:func:`operandize` manufactures the zero-slot cotangent structure the
model's custom-vjp sites thread real operands through — per leaf, shaped by
the plan's ``group`` kind (``expert_tokens`` supplies the MoE capacity
token count, which differs from the flattened batch token count).

MCU variants (paper §4): V1/V2/V3 have identical *step-level* numerics (the
ISA simulator models their scheduling/energy differences); the trainer
records the variant for the benchmark layer.

Which leaves live as planes — and at which per-leaf slice spec, gradient
path, operand group kind, and ADC configuration — is decided by a resolved
``repro.plan`` tree (pass ``plan=`` to ``init``/``update``/``operandize``/
...); with no plan the behavior-preserving ``repro.plan.default_rules(cfg)``
applies (matrix dims [-2:] >= ``min_dim``, float dtype, single-use matmul
weights flow operands). ``repro.plan.coverage_rules`` extends the mapping to
conv/einsum/MoE weights; ``benchmarks/coverage_report.py`` accounts for the
analog-FLOPs fraction each plan achieves.
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import (
    DEFAULT_SPEC,
    SliceSpec,
    choose_frac_bits,
    dequantize_planes,
    saturation_fraction,
    slice_weights,
)
from repro.core.fixed_point import quantize
from repro.kernels.crs import crs as crs_op
from repro.kernels.sliced_opa import opa_deposit, opa_device_update, opa_fused_update
from repro.models.common import (
    OuterProductGrad,
    XbarWeight,
    is_outer_product_grad,
    path_str as _leaf_path_str,
)
from repro.plan import default_rules, operand_eligible_path, resolve_plan


@dataclasses.dataclass(frozen=True)
class PantherConfig:
    spec: SliceSpec = DEFAULT_SPEC
    crs_every: int = 1024
    stochastic_round: bool = True
    momentum: float = 0.0  # optional digital-VFU momentum (paper uses plain SGD)
    min_ndim: int = 2  # crossbar-map params with ndim >= this
    min_dim: int = 8  # ... and every dim >= this (conv taps etc. stay digital)
    variant: str = "v2"  # informational: v1 (SGD), v2 (mini-batch), v3 (large-batch)
    margin_bits: int = 2  # headroom when choosing the per-tensor scale
    compute_dtype: Any = jnp.float32
    # Stochastic-rounding noise source, threaded identically to the dense
    # quantize+deposit path and the fused operand kernel so the two pipelines
    # stay bit-compatible: "counter" (default; stateless coordinate hash,
    # generated in-kernel, bit-reproducible everywhere), "grid" (legacy PR 1-5
    # U[0,1) HBM grid — old checkpoints replay bit-identically), "hw" (TPU
    # hardware PRNG in-kernel; fastest, not replayable off-TPU).
    rng_mode: str = "counter"
    # OPA / CRS kernel dispatch override (None = auto: Pallas on TPU, jnp ref
    # on CPU and under a mesh). Tests force (True, True) to run the kernels
    # in interpret mode; the ref path is bit-identical to dense-grad +
    # opa_deposit.
    opa_use_kernel: bool | None = None
    opa_interpret: bool | None = None


class SlicedTensor(NamedTuple):
    """Optimizer-side state of one crossbar-mapped parameter."""

    planes: jax.Array  # int8 [S, *shape]
    frac_bits: jax.Array  # int32 scalar: weight grid = 2^-F


class PantherState(NamedTuple):
    step: jax.Array
    sliced: Any  # pytree: SlicedTensor | None per param leaf
    momentum: Any  # pytree: float buffer | None  (digital VFU)


def _leaf_device(pl):
    """The write-path ``DeviceModel`` a plan leaf carries (None when the leaf
    has no fidelity, no device, or an ideal write path)."""
    if pl is None or pl.fidelity is None or pl.fidelity.device is None:
        return None
    dev = pl.fidelity.device
    return dev if dev.writes_nonideal() else None


def tiki_taka(cfg: PantherConfig = PantherConfig(), beta: float = 0.875) -> PantherConfig:
    """Tiki-Taka-style noise-resilient training config (Gokmen & Haensch,
    analog RPU line): gradients accumulate in a digital buffer and the
    *averaged* update is what gets written to the noisy device, so the i.i.d.
    per-step write noise averages down by ~sqrt(1/(1-beta)) while the signal
    accumulates — the momentum-on-device rule the device sweep in
    ``benchmarks/fig9_slice_crs.py`` benchmarks against plain sliced SGD at
    matched ``DeviceModel`` noise. Rides ``PantherConfig.momentum`` (the
    digital-VFU buffer), so it composes with any ``repro.plan`` rule set —
    ``default_rules(tiki_taka(cfg), fidelity=fid_with_device)`` is the whole
    recipe. Operand-form gradients materialize into the buffer (momentum is
    dense by nature); the deposit still applies the full device write
    physics."""
    return dataclasses.replace(cfg, momentum=beta, variant="tiki-taka")


def _crs(planes, spec, cfg: PantherConfig):
    return crs_op(planes, spec, use_kernel=cfg.opa_use_kernel, interpret=cfg.opa_interpret)


def _default_plan(params, cfg: PantherConfig):
    """The behavior-preserving plan (repro.plan.default_rules): matrix-shaped
    float leaves map to planes at ``cfg.spec``; everything else is digital."""
    return resolve_plan(params, default_rules(cfg))


def _plan_leaves(plan, treedef, n: int):
    """Per-leaf ``LeafPlan | None`` aligned with a flattened grads tree."""
    if plan is None:
        return [None] * n
    return treedef.flatten_up_to(plan)


def _grad_leaf(x) -> bool:
    """Treat an OuterProductGrad node as ONE gradient leaf when flattening a
    grads tree — keeps leaf indexing (and so per-leaf stochastic-rounding
    keys) identical between the dense and operand pipelines."""
    return is_outer_product_grad(x)


def _opa_operand_update(planes, g, lr, frac_bits, spec, **kwargs):
    """``opa_fused_update`` for any operand kind. An ``"im2col"`` operand
    carries the channel axis in its stack with per-channel ``[K, 1]`` outer
    products, while the leaf's planes are stored ``[S, ..., K, C]`` — so the
    deposit runs on the transposed channel-as-stack view ``[S, ..., C, K,
    1]`` and transposes back. The reshuffle is an elementwise bijection:
    deposit numerics are unchanged, and the caller applies CRS on the
    original stored layout."""
    if getattr(g, "kind", "matmul") != "im2col":
        return opa_fused_update(planes, g.x, g.dh, lr, frac_bits, spec, **kwargs)
    lead = planes.ndim - 3  # [S, *lead, K, C]
    p2 = jnp.moveaxis(planes, -1, 1 + lead)[..., None]
    p2 = opa_fused_update(p2, g.x, g.dh, lr, frac_bits, spec, **kwargs)
    return jnp.moveaxis(p2[..., 0], 1 + lead, -1)


def _fid_leaves(s: SlicedTensor, stack: tuple):
    """Planes/frac_bits of one leaf, re-laid-out for the layer scan: the S
    slice dim moves behind the ``stack`` dims (lax.scan slices the leading
    layer axis of every XbarWeight child) and the scalar frac_bits broadcasts
    over the stack so each scanned layer carries its own copy."""
    planes = jnp.moveaxis(s.planes, 0, len(stack))
    frac = jnp.broadcast_to(s.frac_bits, stack)
    return planes, frac


def _operand_slots(p, group: str | None, tokens: int, expert_tokens: int | None, act_dtype):
    """Zero cotangent slots matching what the model's xbar site will emit for
    this leaf — the custom-vjp aval contract is exact, so each group kind
    gets its own layout (see the module docstring for the shapes)."""
    stack = p.shape[:-2]
    if group == "im2col":
        # p [*lead, K, C]: per-channel [K, 1] outer products over the window
        xz = jnp.zeros((*stack, p.shape[-1], tokens, p.shape[-2]), act_dtype)
        dhz = jnp.zeros((*stack, p.shape[-1], tokens, 1), act_dtype)
        return OuterProductGrad(xz, dhz, kind="im2col")
    t = expert_tokens if (group == "expert" and expert_tokens is not None) else tokens
    xz = jnp.zeros((*stack, t, p.shape[-2]), act_dtype)
    dhz = jnp.zeros((*stack, t, p.shape[-1]), act_dtype)
    return OuterProductGrad(xz, dhz)


def operandize(params, sliced, tokens: int, act_dtype, fid=None, plan=None,
               expert_tokens: int | None = None):
    """Wrap operand-eligible crossbar leaves of a materialized param tree in
    ``XbarWeight`` so the model's backward returns ``OuterProductGrad``
    weight cotangents instead of dense ``[M, N]`` matrices.

    ``tokens`` is the flattened token count per differentiated forward (one
    microbatch: ``B * S``); the zero slots give the custom-vjp backward a
    matching cotangent structure to thread the real operands through. The
    slot layout follows the plan leaf's ``group`` kind: matmul leaves stash
    ``[T, M]``/``[T, N]`` factors, ``"im2col"`` conv taps stash windowed
    patch operands, and ``"expert"`` MoE banks stash per-expert capacity
    buffers of ``expert_tokens`` tokens (the MoE dispatch capacity
    ``G * C``, which the train step computes from its MoE config — required
    because the custom-vjp cotangent aval must match exactly).
    Eligibility: the leaf has optimizer planes (``sliced`` non-None) and
    either its resolved ``plan`` leaf says ``grad="operand"`` or — with no
    plan — its path passes the default operand rule
    (``repro.plan.operand_eligible_path``: single-use matmul weights only).

    With ``fid`` (a ``FidelityConfig``, or per-leaf ``plan.fidelity``), each
    wrap additionally carries the leaf's digit planes + frac_bits so the
    ``xbar_*`` sites read them through the finite-ADC engine — forward MVM,
    backward MᵀVM ``dx`` — while the weight cotangent stays in operand form
    for the fused OPA deposit: the model trains against the same crossbar
    state the optimizer writes.
    """
    if plan is not None and fid is not None:
        raise ValueError("pass fidelity per-leaf through the plan, not both")

    def wrap(path, p, s, pl):
        if s is None:
            return p
        if pl is not None:
            if pl.grad != "operand":
                return p
            leaf_fid = pl.fidelity
            group = pl.group
        else:
            if not operand_eligible_path(_leaf_path_str(path)):
                return p
            leaf_fid = fid
            group = None
        g = _operand_slots(p, group, tokens, expert_tokens, act_dtype)
        if leaf_fid is None:
            return XbarWeight(p, g)
        planes, frac = _fid_leaves(s, p.shape[:-2])
        return XbarWeight(p, g, planes=planes, frac_bits=frac, fid=leaf_fid)

    if plan is None:
        return jax.tree_util.tree_map_with_path(
            lambda path, p, s: wrap(path, p, s, None), params, sliced
        )
    return jax.tree_util.tree_map_with_path(wrap, params, sliced, plan)


def fidelitize(params, sliced, fid=None, plan=None):
    """Forward-only fidelity wrap for serving: operand-eligible leaves of a
    materialized param tree become ``XbarWeight(w, None, planes, frac_bits,
    fid)`` so prefill/decode read the crossbar through the finite-ADC engine
    (no gradient slots — do not differentiate through the result; use
    ``operandize`` with fidelity inside the train step for that). With a
    resolved ``plan``, each leaf uses its own ``plan.fidelity`` (leaves
    without one serve the lossless dequantized fast path) — heterogeneous
    per-layer ADC as a serving mode."""
    if plan is not None and fid is not None:
        raise ValueError("pass fidelity per-leaf through the plan, not both")

    def wrap(path, p, s, pl):
        if s is None:
            return p
        if pl is not None:
            leaf_fid = pl.fidelity if pl.grad == "operand" else None
        else:
            leaf_fid = fid if operand_eligible_path(_leaf_path_str(path)) else None
        if leaf_fid is None:
            return p
        planes, frac = _fid_leaves(s, p.shape[:-2])
        return XbarWeight(p, None, planes=planes, frac_bits=frac, fid=leaf_fid)

    if plan is None:
        return jax.tree_util.tree_map_with_path(
            lambda path, p, s: wrap(path, p, s, None), params, sliced
        )
    return jax.tree_util.tree_map_with_path(wrap, params, sliced, plan)


def strip_operand_grads(grads):
    """Normalize a cotangent tree from an operandized step: ``XbarWeight``
    cotangents (identically-zero dense leaf + real operands) become bare
    ``OuterProductGrad`` leaves; everything else passes through. The dropped
    zeros leaf is dead code XLA eliminates."""
    return jax.tree.map(
        lambda g: g.g if isinstance(g, XbarWeight) else g,
        grads,
        is_leaf=lambda x: isinstance(x, XbarWeight),
    )


def global_grad_norm(grads) -> jax.Array:
    """Global L2 norm over a mixed dense/operand gradient tree. Operand
    leaves use the Gram-matrix identity (no ``[M, N]`` materialization)."""
    total = jnp.zeros((), jnp.float32)
    for g in jax.tree.leaves(grads, is_leaf=_grad_leaf):
        if is_outer_product_grad(g):
            total = total + g.sq_norm()
        else:
            total = total + jnp.sum(g.astype(jnp.float32) ** 2)
    return jnp.sqrt(total)


def init(params, cfg: PantherConfig = PantherConfig(), plan=None) -> PantherState:
    """``plan`` (a resolved ``repro.plan`` tree) decides which leaves get
    planes and at which per-leaf :class:`SliceSpec`; ``None`` resolves the
    behavior-preserving default plan from ``cfg``.

    A state initialized under a heterogeneous plan must be driven with the
    SAME plan everywhere (``update``/``update_split``/``saturation_report``):
    plan-less calls fall back to ``cfg.spec`` rails for deposits and CRS,
    which silently mis-clip planes sliced under a different spec (the two
    layouts share S, so no shape error fires). Checkpoints persist the plan
    (``save_checkpoint(plan=...)``) so restores validate this; in-process,
    threading the plan is the caller's contract."""
    if plan is None:
        plan = _default_plan(params, cfg)

    def init_leaf(p, pl):
        if not pl.mapped:
            return None
        f = choose_frac_bits(p, margin_bits=cfg.margin_bits)
        q = quantize(p, f)
        return SlicedTensor(planes=slice_weights(q, pl.spec), frac_bits=f)

    sliced = jax.tree.map(init_leaf, params, plan)
    mom = jax.tree.map(lambda p: jnp.zeros_like(p) if cfg.momentum > 0 else None, params)
    return PantherState(step=jnp.zeros((), jnp.int32), sliced=sliced, momentum=mom)


def materialize(params, state: PantherState, cfg: PantherConfig = PantherConfig()):
    """Dequantize the sliced state into compute-dtype parameters.

    The returned tree is what the forward/backward runs on (the paper's MVM /
    MᵀVM read the same crossbar cells the OPA writes).
    """

    def mat_leaf(p, s):
        if s is None:
            return p
        return dequantize_planes(s.planes, s.frac_bits, cfg.spec, dtype=cfg.compute_dtype)

    return jax.tree.map(mat_leaf, params, state.sliced, is_leaf=lambda x: x is None or isinstance(x, SlicedTensor))


def update(
    grads,
    state: PantherState,
    params,
    lr: jax.Array,
    cfg: PantherConfig = PantherConfig(),
    rng: jax.Array | None = None,
    plan=None,
):
    """One PANTHER step. Returns (new_params, new_state).

    grads/params are float trees; the sliced leaves' float values are
    regenerated from the planes after the OPA deposit (single source of
    truth = the crossbar state). ``plan`` supplies per-leaf slice specs
    (heterogeneous crossbars); ``None`` uses ``cfg.spec`` everywhere.
    """
    step = state.step
    do_crs = (step % cfg.crs_every) == (cfg.crs_every - 1)
    base_key = rng if rng is not None else jax.random.PRNGKey(0)
    base_key = jax.random.fold_in(base_key, step)

    leaves_g, treedef = jax.tree.flatten(grads, is_leaf=_grad_leaf)
    leaves_p = treedef.flatten_up_to(params)
    leaves_s = treedef.flatten_up_to(state.sliced)
    leaves_m = treedef.flatten_up_to(state.momentum)
    leaves_pl = _plan_leaves(plan, treedef, len(leaves_g))

    new_p, new_s, new_m = [], [], []
    for i, (g, p, s, m, pl) in enumerate(
        zip(leaves_g, leaves_p, leaves_s, leaves_m, leaves_pl)
    ):
        spec = pl.spec if pl is not None else cfg.spec
        if is_outer_product_grad(g) and (s is None or (cfg.momentum > 0 and m is not None)):
            g = g.materialize()  # momentum/VFU buffers are dense by nature
        if cfg.momentum > 0 and m is not None:
            m = cfg.momentum * m + g
            g_eff = m
        else:
            g_eff = g
        if s is None:
            new_p.append((p - lr * g_eff).astype(p.dtype))
            new_s.append(None)
            new_m.append(m)
            continue
        key = jax.random.fold_in(base_key, i)
        dev = _leaf_device(pl)
        if is_outer_product_grad(g_eff):
            # operand path: X^T@dH -> quantize -> deposit in one fused pass
            planes = _opa_operand_update(
                s.planes, g_eff, lr, s.frac_bits, spec,
                stochastic=cfg.stochastic_round, key=key, rng_mode=cfg.rng_mode,
                use_kernel=cfg.opa_use_kernel, interpret=cfg.opa_interpret,
                device=dev,
            )
        elif dev is not None:
            # dense gradient onto a write-nonideal device: same physics
            # pipeline as the fused path, on the materialized gradient
            planes = opa_device_update(
                s.planes, g_eff, lr, s.frac_bits, spec, device=dev,
                stochastic=cfg.stochastic_round, key=key,
                rng_mode=cfg.rng_mode if cfg.rng_mode != "hw" else "counter",
                use_kernel=cfg.opa_use_kernel, interpret=cfg.opa_interpret,
            )
        else:
            # dense path: quantize -lr*g onto the weight grid, deposit. The
            # "hw" draw exists only inside the fused kernel; dense leaves
            # then take the (equally in-kernel-generatable) counter draw.
            upd = quantize(
                -lr * g_eff.astype(jnp.float32),
                s.frac_bits,
                stochastic=cfg.stochastic_round,
                key=key,
                rng_mode=cfg.rng_mode if cfg.rng_mode != "hw" else "counter",
            )
            planes = opa_deposit(
                s.planes, upd, spec,
                use_kernel=cfg.opa_use_kernel, interpret=cfg.opa_interpret,
            )
        planes = jax.lax.cond(
            do_crs, lambda x, _s=spec: _crs(x, _s, cfg), lambda x: x, planes
        )
        new_sliced = SlicedTensor(planes=planes, frac_bits=s.frac_bits)
        new_s.append(new_sliced)
        new_m.append(m)
        new_p.append(dequantize_planes(planes, s.frac_bits, cfg.spec, dtype=p.dtype))

    return (
        jax.tree.unflatten(treedef, new_p),
        PantherState(
            step=step + 1,
            sliced=jax.tree.unflatten(treedef, new_s),
            momentum=jax.tree.unflatten(treedef, new_m),
        ),
    )


# --------------------- split-state API (production trainer) -----------------
# The trainer does not store a float copy of crossbar-mapped weights: the
# int8 planes are the single source of truth (exactly the accelerator's
# memory layout). ``digital`` holds only the VFU-path leaves.


def _is_none_or_leaf(x):
    return x is None or isinstance(x, (SlicedTensor, jax.Array)) or hasattr(x, "shape")


def init_split(params, cfg: PantherConfig = PantherConfig(), plan=None):
    """-> (digital, sliced): complementary trees (None at the other's leaves).

    ``plan`` (resolved ``repro.plan`` tree) decides the partition and the
    per-leaf slice spec; ``None`` resolves the default plan from ``cfg``."""
    if plan is None:
        plan = _default_plan(params, cfg)

    def split(p, pl):
        if pl.mapped:
            f = choose_frac_bits(p, margin_bits=cfg.margin_bits)
            return (None, SlicedTensor(planes=slice_weights(quantize(p, f), pl.spec), frac_bits=f))
        return (p, None)

    pairs = jax.tree.map(split, params, plan)
    digital = jax.tree.map(lambda pr: pr[0], pairs, is_leaf=lambda x: isinstance(x, tuple))
    sliced = jax.tree.map(lambda pr: pr[1], pairs, is_leaf=lambda x: isinstance(x, tuple))
    return digital, sliced


def materialize_split(digital, sliced, cfg: PantherConfig = PantherConfig()):
    """Rebuild the compute-dtype parameter tree (crossbar read = dequantize)."""

    def pick(d, s):
        if s is None:
            return d
        return dequantize_planes(s.planes, s.frac_bits, cfg.spec, dtype=cfg.compute_dtype)

    return jax.tree.map(pick, digital, sliced, is_leaf=lambda x: x is None or isinstance(x, SlicedTensor))


def update_split(grads, digital, sliced, step, lr, cfg: PantherConfig = PantherConfig(),
                 rng=None, plan=None):
    """One OPA step on the split state. Returns (digital', sliced').

    Gradient leaves may be dense arrays (VFU path / non-operand crossbar
    leaves: quantize + ``opa_deposit``) or ``OuterProductGrad`` operands
    (``opa_fused_update``: the ``[M, N]`` gradient never materializes).
    Leaf enumeration — and therefore each leaf's stochastic-rounding key —
    is identical in both modes, so the two pipelines are bit-compatible.
    ``plan`` supplies per-leaf slice specs (heterogeneous crossbars);
    ``None`` uses ``cfg.spec`` everywhere.

    The dequantized new params are *not* returned — the next step
    re-materializes from the planes, so XLA dead-code-eliminates any unused
    dequantization (no redundant HBM traffic).
    """
    do_crs = (step % cfg.crs_every) == (cfg.crs_every - 1)
    base_key = rng if rng is not None else jax.random.PRNGKey(0)
    base_key = jax.random.fold_in(base_key, step)

    leaves_g, treedef = jax.tree.flatten(grads, is_leaf=_grad_leaf)
    leaves_d = treedef.flatten_up_to(digital)
    leaves_s = treedef.flatten_up_to(sliced)
    leaves_pl = _plan_leaves(plan, treedef, len(leaves_g))
    new_d, new_s = [], []
    for i, (g, d, s, pl) in enumerate(zip(leaves_g, leaves_d, leaves_s, leaves_pl)):
        if s is None:
            if is_outer_product_grad(g):
                g = g.materialize()
            new_d.append((d - lr * g.astype(d.dtype)).astype(d.dtype))
            new_s.append(None)
            continue
        spec = pl.spec if pl is not None else cfg.spec
        key = jax.random.fold_in(base_key, i)
        dev = _leaf_device(pl)
        if is_outer_product_grad(g):
            planes = _opa_operand_update(
                s.planes, g, lr, s.frac_bits, spec,
                stochastic=cfg.stochastic_round, key=key, rng_mode=cfg.rng_mode,
                use_kernel=cfg.opa_use_kernel, interpret=cfg.opa_interpret,
                device=dev,
            )
        elif dev is not None:
            planes = opa_device_update(
                s.planes, g, lr, s.frac_bits, spec, device=dev,
                stochastic=cfg.stochastic_round, key=key,
                rng_mode=cfg.rng_mode if cfg.rng_mode != "hw" else "counter",
                use_kernel=cfg.opa_use_kernel, interpret=cfg.opa_interpret,
            )
        else:
            upd = quantize(
                -lr * g.astype(jnp.float32), s.frac_bits,
                stochastic=cfg.stochastic_round, key=key,
                rng_mode=cfg.rng_mode if cfg.rng_mode != "hw" else "counter",
            )
            planes = opa_deposit(
                s.planes, upd, spec,
                use_kernel=cfg.opa_use_kernel, interpret=cfg.opa_interpret,
            )
        planes = jax.lax.cond(
            do_crs, lambda x, _s=spec: _crs(x, _s, cfg), lambda x: x, planes
        )
        new_d.append(None)
        new_s.append(SlicedTensor(planes=planes, frac_bits=s.frac_bits))
    return jax.tree.unflatten(treedef, new_d), jax.tree.unflatten(treedef, new_s)


def saturation_report(state: PantherState, cfg: PantherConfig = PantherConfig(), plan=None):
    """Per-parameter per-plane saturation fractions (paper Fig 9 metric)."""

    def rep(s, pl=None):
        if s is None:
            return None
        return saturation_fraction(s.planes, pl.spec if pl is not None else cfg.spec)

    is_leaf = lambda x: x is None or isinstance(x, SlicedTensor)
    if plan is None:
        return jax.tree.map(rep, state.sliced, is_leaf=is_leaf)
    return jax.tree.map(rep, state.sliced, plan, is_leaf=is_leaf)
