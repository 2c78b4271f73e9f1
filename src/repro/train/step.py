"""Production train step: bf16 forward/backward on dequantized crossbar
state + PANTHER OPA update. Built once per (config, mesh); pjit-ready.

Memory layout per crossbar-mapped weight: int8 planes [S, *w] (source of
truth, 8 B/param at the default 8-slice spec — the paper's §6.3 configuration)
+ transient bf16 compute copy inside the step. No fp32 master copy exists —
the planes ARE the master (32-bit fixed point, as in the accelerator).

Gradient-operand pipeline (default, ``operand_grads=True``): single-use
matmul weights (attention wqkv/wo — q/k/v fused so their shared layer input
is stashed once, MLA projections, gated-MLP wi_gate/wi_up/wo) are wrapped in
``models.common.XbarWeight`` so the
backward returns ``OuterProductGrad(x, dh)`` — the paper's in-crossbar
outer-product operands — instead of a dense ``[M, N]`` matrix. The
optimizer feeds the operands to ``kernels.sliced_opa.opa_fused_update``
(quantize + deposit fused with the MXU contraction: the weight gradient
never exists in HBM), microbatch accumulation concatenates per-microbatch
token tiles through the gradient scan's stacked outputs, and the grad-norm
metric comes from the Gram identity ``||X^T dH||_F^2 = <XX^T, dHdH^T>``.
Under ``repro.plan.coverage_rules`` the operand pipeline extends past plain
linears: depthwise-conv taps flow ``kind="im2col"`` patch operands,
Mamba2/xLSTM projections flow matmul operands, and MoE expert banks flow
grouped per-expert operands (``expert_tokens`` capacity buffers). Remaining
dense-grad leaves: embeddings / tied LM head (gather + multi-use
cotangents), zamba/MoE ``shared`` weights (multi-invocation — operand
cotangents do not sum), and sLSTM's recurrent ``r`` (per-step cell reuse);
they take the seed quantize + ``opa_deposit`` path, which is bit-compatible
per leaf.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import plan as planlib
from repro.distributed import sharding as shd
from repro.models import lm
from repro.models.common import LMConfig, OuterProductGrad, XbarWeight
from repro.optim import PantherConfig, panther


# Named scopes of the step's own phases (``jax.named_scope``): their names
# reach every HLO instruction's metadata (op_name), so a device trace can
# attribute time to the plane dequantize and to the update.
DEQUANTIZE_SCOPE = "step.dequantize"
UPDATE_SCOPE = "step.update"


def _is_opg(x) -> bool:
    return isinstance(x, OuterProductGrad)


def _is_xw(x) -> bool:
    return isinstance(x, XbarWeight)


class TrainState(NamedTuple):
    step: jax.Array
    digital: Any  # float leaves (VFU path); None at crossbar leaves
    sliced: Any  # SlicedTensor leaves; None at digital leaves
    rng: jax.Array


def train_state_init(cfg: LMConfig, opt_cfg: PantherConfig, key, plan=None) -> TrainState:
    """``plan`` (a resolved ``repro.plan`` tree over the param tree) selects
    which leaves live as digit planes and at which per-leaf slice spec."""
    params = lm.init_params(cfg, key)
    digital, sliced = panther.init_split(params, opt_cfg, plan=plan)
    return TrainState(
        step=jnp.zeros((), jnp.int32), digital=digital, sliced=sliced, rng=jax.random.PRNGKey(7)
    )


def train_state_specs(cfg: LMConfig, opt_cfg: PantherConfig, mesh=None, fsdp: bool = False,
                      plan=None):
    """PartitionSpec pytree for TrainState (planes shard like their matrix
    with a leading None for the slice dim). With ``fsdp``, planes
    additionally shard an unsharded axis over 'data' (ZeRO-3). ``plan``
    supplies per-leaf shard hints overriding the name rules."""
    shapes = jax.eval_shape(lambda: train_state_init(cfg, opt_cfg, jax.random.PRNGKey(0), plan=plan))
    dsize = mesh.shape["data"] if (fsdp and mesh is not None) else 1
    hints = {}
    if plan is not None:
        hints = {p: pl.shard for p, pl in planlib.plan_by_path(plan).items()}

    def digital_spec(path, leaf):
        ps = shd._path_str(path)
        s = shd.leaf_spec(ps, leaf.ndim, hint=hints.get(ps))
        if mesh is not None:
            s = shd.sanitize_spec(s, leaf.shape, mesh)
        return s

    def sliced_spec(path, leaf):
        ps = shd._path_str(path)
        if ps.endswith("frac_bits"):
            return P()
        # planes [S, *w] shard like their matrix w (strip the /planes suffix
        # so the name rules see the parameter path), S replicated
        ppath = ps.removesuffix("/planes")
        hint = hints.get(ppath)
        base = shd.leaf_spec(ppath, leaf.ndim - 1, hint=hint)
        full = P(*((None,) + tuple(base)))
        if mesh is not None:
            full = shd.sanitize_spec(full, leaf.shape, mesh)
        if fsdp:
            # FSDP only on the trailing matrix axes (never S or scan stacks)
            n_tail = len(shd.trailing_spec(ppath, hint=hint)) or 2
            full = shd.fsdp_spec(full, leaf.shape, dsize, n_tail=n_tail)
        return full

    return TrainState(
        step=P(),
        digital=jax.tree_util.tree_map_with_path(digital_spec, shapes.digital),
        sliced=jax.tree_util.tree_map_with_path(sliced_spec, shapes.sliced),
        rng=P(),
    )


def grad_specs(
    cfg: LMConfig,
    opt_cfg: PantherConfig,
    mesh=None,
    fsdp: bool = False,
    operand: bool = False,
    mb_batch: int | None = None,
    plan=None,
):
    """Gradient sharding (mirrors the stored planes minus the S dim) —
    pinning this keeps the f32 accumulation buffer ZeRO-sharded instead of
    letting SPMD fall back to TP-only (which blows HBM on 34B models).

    Eligibility comes from the resolved mapping ``plan`` (default plan of
    ``opt_cfg`` when ``None``). With ``operand=True``, operand crossbar
    leaves get an ``OuterProductGrad`` of specs instead (token axis over the
    DP axes, feature axes inheriting the weight's own M/N rules) — operands
    are activation-shaped, so they never need the ZeRO transform."""
    shapes = jax.eval_shape(lambda: lm.init_params(cfg, jax.random.PRNGKey(0)))
    if plan is None:
        plan = planlib.resolve_plan(shapes, planlib.default_rules(opt_cfg))
    by_path = planlib.plan_by_path(plan)
    dsize = mesh.shape["data"] if (fsdp and mesh is not None) else 1

    def spec(path, leaf):
        ps = shd._path_str(path)
        pl = by_path.get(ps)
        hint = pl.shard if pl is not None else None
        mapped = pl is not None and pl.mapped
        if operand and mapped and pl.grad == "operand":
            return shd.operand_grad_spec(ps, leaf.shape, mesh, mb_batch, hint=hint,
                                         group=pl.group)
        base = shd.leaf_spec(ps, leaf.ndim, hint=hint)
        if mesh is not None:
            base = shd.sanitize_spec(base, leaf.shape, mesh)
        if fsdp and mapped:
            n_tail = len(shd.trailing_spec(ps, hint=hint)) or 2
            base = shd.fsdp_spec(base, leaf.shape, dsize, n_tail=n_tail)
        return base

    return jax.tree_util.tree_map_with_path(spec, shapes)


def batch_specs(cfg: LMConfig, mesh, global_batch: int, microbatches: int = 1):
    mb = global_batch // microbatches
    lead = (None,) if microbatches > 1 else ()
    b2 = shd.data_spec(mesh, mb, 2)
    b3 = shd.data_spec(mesh, mb, 3)
    b = P(*(lead + tuple(b2)))
    if cfg.input_mode == "tokens":
        return {"inputs": b, "labels": b}
    return {"inputs": P(*(lead + tuple(b3))), "labels": b}


def make_train_step(
    cfg: LMConfig,
    opt_cfg: PantherConfig,
    lr_schedule,
    mesh=None,
    global_batch: int | None = None,
    remat="full",
    microbatches: int = 1,
    fsdp: bool = False,
    grad_dtype=jnp.float32,
    operand_grads: bool = True,
    fidelity=None,
    plan=None,
    plan_rules=None,
    stash_fallback: bool = False,
):
    """Returns ``train_step(state, batch) -> (state', metrics)``.

    Under a mesh, activations get explicit batch-sharding constraints and
    logits are constrained to keep the vocab dim on 'model' (never gathering
    the [B,S,V] tensor). ``microbatches > 1`` expects the batch leaves
    pre-shaped [G, B/G, ...] and accumulates gradients over a lax.scan —
    the standard activation-memory lever (paper variant-2 semantics: one
    weight update per global batch).

    ``operand_grads`` selects the fused outer-product pipeline (module
    docstring); ``False`` is the seed dense-grad path, kept for
    equivalence testing and as a fallback.

    ``cfg.fidelity`` (a ``models.common.FidelityConfig``; the legacy
    ``fidelity=`` argument was removed and now raises ``TypeError`` — attach
    fidelity through the plan) turns on crossbar-in-the-loop training: operand-
    eligible linears run their forward through the packed finite-ADC
    sliced-MVM engine and their ``dx`` backward through the MᵀVM transpose
    read, on the SAME int8 planes the OPA deposit writes — the Fig-9/10
    study for gradients. The differentiated param tree then carries integer
    plane leaves, so AD runs with ``allow_int`` (their cotangents are
    float0, stripped with the operand zeros). Fidelity requires
    ``operand_grads``. Under a ``mesh`` the whole loop runs pjit-sharded
    (the paper's multi-core/multi-tile regime): the step traces inside a
    ``distributed.fidelity`` ShardCtx, so every engine read lowers through
    the shard_map path — token axis over the DP axes, crossbar tile blocks
    over 'model' per each leaf's ``FidelityConfig.shard_dim`` (attached here
    from the plan shard hints / name rules via
    ``plan.attach_fidelity_shard_dims``), contraction-side partials (the
    forward's row-block shift-and-add, the MᵀVM ``dx`` column partials)
    psum-reduced exactly. The transient plane/scale leaves the wraps carry
    get sharding constraints mirroring the stored planes
    (``sharding.fidelity_plane_specs``), so the reads, the OPA deposit, and
    the optimizer state agree on one layout.

    ``plan`` / ``plan_rules`` select the declarative per-leaf mapping
    (``repro.plan``): pass a resolved plan tree, or an ordered
    ``PlanRule`` list resolved here against the param shapes (token-
    dependent rules see the real per-microbatch token count at trace time).
    The plan is the single source of truth for eligibility, per-leaf slice
    spec, per-leaf fidelity, and shard hints — heterogeneous crossbar
    configurations per layer (paper Fig. 10). ``stash_fallback`` appends
    ``repro.plan.operand_stash_rule`` to the default rules: leaves whose
    operand stash would outweigh the dense gradient fall back to the
    (bit-compatible) dense deposit path."""
    if fidelity is not None:
        raise TypeError(
            "make_train_step(fidelity=...) was removed; pass plan_rules="
            "repro.plan.default_rules(opt_cfg, fidelity=...) (or a resolved plan=)"
        )
    fidelity = cfg.fidelity
    if (plan is not None or plan_rules is not None) and fidelity is not None:
        raise ValueError("with an explicit plan, attach fidelity per-leaf via "
                         "PlanRule(fidelity=...) instead of cfg.fidelity")
    if plan is not None and plan_rules is not None:
        raise ValueError("pass either a resolved plan or plan_rules, not both")
    if stash_fallback and (plan is not None or plan_rules is not None):
        # an explicit plan/rule list owns its rule set: appending behind the
        # caller's back would reorder overrides — append operand_stash_rule()
        # to the rules (or resolve it into the plan) instead
        raise ValueError("stash_fallback only augments the default rules; "
                         "append repro.plan.operand_stash_rule() to your "
                         "plan_rules (or resolve it into your plan) directly")
    if mesh is not None and opt_cfg.opa_use_kernel is None:
        # Mosaic cannot partition a pallas_call and the optimizer kernels
        # (fused OPA, deposit, CRS) are not wrapped in a shard_map yet: under
        # a mesh the update runs their SPMD-partitionable jnp references
        opt_cfg = dataclasses.replace(opt_cfg, opa_use_kernel=False)
    if fidelity is not None and fidelity.spec != opt_cfg.spec:
        raise ValueError(
            f"FidelityConfig.spec {fidelity.spec} must match the optimizer "
            f"plane layout {opt_cfg.spec}"
        )

    # Abstract param shapes, traced at most once per build (the initializer
    # trace is nontrivial on multi-B configs and up to three sites need it).
    _shapes_memo = []

    def param_shapes():
        if not _shapes_memo:
            _shapes_memo.append(
                jax.eval_shape(lambda: lm.init_params(cfg, jax.random.PRNGKey(0)))
            )
        return _shapes_memo[0]

    # Static (build-time) plan: shard/eligibility decisions for the mesh
    # specs. Rules re-resolve at trace time with the real token count so
    # token-dependent rules (operand-stash fallback) can flip leaves.
    rules = tuple(plan_rules) if plan_rules is not None else None
    if rules is None and plan is None and (stash_fallback or fidelity is not None):
        # cfg.fidelity rides the equivalent default rule set — byte-identical
        # to the old direct threading (test_uniform_plan_fidelity_matches_legacy_arg)
        rules = planlib.default_rules(opt_cfg, fidelity=fidelity,
                                      stash_fallback=stash_fallback)
        fidelity = None  # rides the plan from here on
    plan0 = plan
    if plan0 is None and rules is not None:
        plan0 = planlib.resolve_plan(param_shapes(), rules)
    use_plan = plan0 is not None

    has_fid = fidelity is not None or (
        use_plan and any(pl.fidelity is not None
                         for pl in planlib.plan_by_path(plan0).values())
    )
    if has_fid:
        if not operand_grads:
            raise ValueError("fidelity mode rides the operand pipeline (operand_grads=True)")
        if mesh is not None:
            # Sharded fidelity: everything rides a resolved plan so each
            # fidelity leaf can carry its tile-shard hint (shard_dim), and
            # the step body traces inside a ShardCtx (below) so the engine
            # reads lower through the shard_map path.
            if plan0 is None:
                plan0 = planlib.resolve_plan(
                    param_shapes(), planlib.default_rules(opt_cfg, fidelity=fidelity)
                )
                fidelity = None  # rides the plan from here on
                use_plan = True
            plan0 = planlib.attach_fidelity_shard_dims(plan0, mesh, param_shapes())
    allow_int = has_fid
    mb_batch = global_batch // microbatches if global_batch else None
    gshard = pshard = None
    gnamed = None
    if mesh is not None and global_batch is not None:
        act_spec = shd.activation_spec(mesh, mb_batch)
        shard_fn = lambda x: jax.lax.with_sharding_constraint(x, NamedSharding(mesh, act_spec))
        gspecs_d = grad_specs(cfg, opt_cfg, mesh=mesh, fsdp=fsdp, plan=plan0)
        if operand_grads:
            gspecs = grad_specs(cfg, opt_cfg, mesh=mesh, fsdp=fsdp,
                                operand=True, mb_batch=mb_batch, plan=plan0)
            # params keep the dense (ZeRO) layout for the compute copy and
            # carry operand-slot specs alongside; fidelity wraps additionally
            # carry plane/scale leaves, whose specs mirror the stored planes
            # (same fid aux as the wraps operandize builds, so the spec tree
            # and the param tree flatten identically)
            if has_fid:
                shapes_p = param_shapes()
                by_path = planlib.plan_by_path(plan0)

                def pspec_leaf(path, d, o, leaf):
                    if not _is_opg(o):
                        return d
                    ps = shd._path_str(path)
                    pl = by_path.get(ps)
                    if pl is None or pl.fidelity is None:
                        return XbarWeight(d, o)
                    planes_s, frac_s = shd.fidelity_plane_specs(
                        ps, leaf.shape, mesh, hint=pl.shard
                    )
                    return XbarWeight(d, o, planes=planes_s, frac_bits=frac_s,
                                      fid=pl.fidelity)

                pspecs = jax.tree_util.tree_map_with_path(
                    pspec_leaf, gspecs_d, gspecs, shapes_p,
                    is_leaf=lambda x: isinstance(x, P),
                )
            else:
                pspecs = jax.tree.map(
                    lambda d, o: XbarWeight(d, o) if _is_opg(o) else d,
                    gspecs_d, gspecs, is_leaf=lambda x: isinstance(x, P),
                )
        else:
            gspecs = pspecs = gspecs_d
        _named = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                                        is_leaf=lambda x: isinstance(x, P))
        gnamed = _named(gspecs)
        pnamed = _named(pspecs)
        gshard = lambda g: jax.tree.map(jax.lax.with_sharding_constraint, g, gnamed)
        pshard = lambda p: jax.tree.map(jax.lax.with_sharding_constraint, p, pnamed)
    else:
        shard_fn = None

    # per-layer weight constraints applied inside the scan bodies
    wshard = None
    if mesh is not None and global_batch is not None:
        wshard = []
        for gi, (name, count) in enumerate(cfg.pattern):
            gsub = pspecs["groups"][gi]

            def mk(gsub=gsub, count=count):
                def f(p_i):
                    def c(spec, leaf):
                        s = tuple(spec)
                        if count > 1 and len(s) > leaf.ndim:  # drop stack axis
                            s = s[1:]
                        s = s + (None,) * (leaf.ndim - len(s))
                        return jax.lax.with_sharding_constraint(
                            leaf, NamedSharding(mesh, P(*s))
                        )

                    return jax.tree.map(c, gsub, p_i, is_leaf=lambda x: isinstance(x, P))

                return f

            wshard.append(mk())

    remat_mode = {"full": True, "dots": "dots", "none": False}.get(remat, remat)

    def loss_of(params, mb):
        return lm.loss_fn(cfg, params, mb, remat=remat_mode, shard_fn=shard_fn, wshard=wshard)

    # Trace-time mesh scope for the fidelity engine: with a ShardCtx active,
    # every fidelity_read in the step (forward MVM, backward MᵀVM) lowers
    # through the shard_map path. No-op without a mesh or without fidelity.
    _fid_scope = contextlib.nullcontext
    if mesh is not None and has_fid:
        from repro.distributed import fidelity as dist_fid

        _fid_ctx = dist_fid.ctx_for(mesh, mb_batch)
        _fid_scope = lambda: dist_fid.use_sharded_fidelity(_fid_ctx)

    def _train_step(state: TrainState, batch):
        with jax.named_scope(DEQUANTIZE_SCOPE):
            params = panther.materialize_split(state.digital, state.sliced, opt_cfg)
            plan_t = plan0
            if operand_grads:
                # flattened tokens per differentiated forward (one microbatch)
                inp = batch["inputs"]
                if cfg.input_mode == "tokens":
                    tokens = inp.shape[-2] * inp.shape[-1]
                else:
                    tokens = inp.shape[-3] * inp.shape[-2]
                # expert-group leaves stash per-expert capacity buffers, not
                # per-token ones: the custom-vjp cotangent aval must match the
                # grouped einsum's dispatch shape exactly, so recompute the MoE
                # capacity token count (G groups x C slots) the model will use
                expert_tokens = None
                if cfg.moe is not None:
                    from repro.models.mlp import MOE_GROUP

                    sg = min(MOE_GROUP, tokens)
                    cap = max(
                        cfg.moe.top_k,
                        int(cfg.moe.capacity_factor * sg * cfg.moe.top_k / cfg.moe.n_experts),
                    )
                    expert_tokens = (tokens // sg) * cap
                if use_plan:
                    # trace-time re-resolution: token-dependent rules (the
                    # operand-stash fallback) see the real microbatch size.
                    # NOT on the mesh path: the sharding specs (gnamed/pnamed)
                    # were built from the build-time plan, and a leaf flipping
                    # operand->dense here would pair a dense gradient with an
                    # OuterProductGrad spec subtree — token-dependent rules are
                    # inert under a mesh (tokens are unknown at spec-build time).
                    if rules is not None and mesh is None:
                        plan_t = planlib.resolve_plan(params, rules, tokens=tokens)
                    params = panther.operandize(params, state.sliced, tokens, cfg.dtype,
                                                plan=plan_t, expert_tokens=expert_tokens)
                else:
                    params = panther.operandize(params, state.sliced, tokens, cfg.dtype,
                                                fid=fidelity)
        if pshard is not None:
            # keep the compute copy ZeRO-sharded in storage; the per-layer
            # all-gather happens inside the layer scan, not up front
            params = pshard(params)

        if microbatches == 1:
            loss_val, grads = jax.value_and_grad(loss_of, allow_int=allow_int)(params, batch)
            if operand_grads:
                grads = panther.strip_operand_grads(grads)
            if gshard is not None:
                grads = gshard(grads)
        elif not operand_grads:
            # grad_dtype=bf16 halves the reduce-scatter bytes and the
            # accumulator footprint (§Perf collective-term lever; the OPA
            # deposit's stochastic rounding keeps the update unbiased)
            gz = jax.tree.map(lambda p: jnp.zeros(p.shape, grad_dtype), params)
            if gshard is not None:
                gz = gshard(gz)

            def mb_body(carry, mb):
                acc_l, acc_g = carry
                l, g = jax.value_and_grad(loss_of)(params, mb)
                if gshard is not None:
                    g = gshard(g)
                acc_g = jax.tree.map(lambda a, x: a + x.astype(grad_dtype), acc_g, g)
                return (acc_l + l, acc_g), None

            (lsum, gsum), _ = jax.lax.scan(mb_body, (jnp.zeros((), jnp.float32), gz), batch)
            loss_val = lsum / microbatches
            grads = jax.tree.map(lambda g: g / microbatches, gsum)
        else:
            # Operand-mode accumulation: dense leaves sum into an f32 carry
            # as before; operand leaves stream out as the scan's stacked ys
            # and concatenate along the token axis afterwards — the
            # accumulator for a crossbar weight is its token tiles, never an
            # [M, N] buffer.
            leaves_p, pdef = jax.tree.flatten(params, is_leaf=_is_xw)
            gname_leaves = pdef.flatten_up_to(gnamed) if gshard is not None else None

            def z(i, p):
                buf = jnp.zeros(p.shape, grad_dtype)
                if gname_leaves is not None:
                    buf = jax.lax.with_sharding_constraint(buf, gname_leaves[i])
                return buf

            acc0 = pdef.unflatten(
                [None if _is_xw(p) else z(i, p) for i, p in enumerate(leaves_p)]
            )

            def mb_body(carry, mb):
                acc_l, acc_g = carry
                l, g = jax.value_and_grad(loss_of, allow_int=allow_int)(params, mb)
                g = panther.strip_operand_grads(g)
                if gshard is not None:
                    g = gshard(g)
                dense_g = jax.tree.map(lambda x: None if _is_opg(x) else x, g, is_leaf=_is_opg)
                op_g = jax.tree.map(lambda x: x if _is_opg(x) else None, g, is_leaf=_is_opg)
                acc_g = jax.tree.map(lambda a, x: a + x.astype(grad_dtype), acc_g, dense_g)
                return (acc_l + l, acc_g), op_g

            (lsum, gsum), ops_y = jax.lax.scan(mb_body, (jnp.zeros((), jnp.float32), acc0), batch)
            loss_val = lsum / microbatches

            def cat(o):
                # [G, *stack, T, d] -> [*stack, G*T, d]: microbatch tiles
                # become extra token tiles of one fused deposit (the token
                # axis is -2 for every operand kind, so this covers im2col
                # and expert-group operands too)
                def m(a):
                    a = jnp.moveaxis(a, 0, -3)
                    return a.reshape(*a.shape[:-3], a.shape[-3] * a.shape[-2], a.shape[-1])

                return OuterProductGrad(m(o.x), m(o.dh), kind=o.kind).scale_dh(1.0 / microbatches)

            ops_merged = jax.tree.map(cat, ops_y, is_leaf=_is_opg)
            leaves_acc = pdef.flatten_up_to(gsum)
            leaves_ops = pdef.flatten_up_to(ops_merged)
            grads = pdef.unflatten(
                [o if a is None else a / microbatches for a, o in zip(leaves_acc, leaves_ops)]
            )

        with jax.named_scope(UPDATE_SCOPE):
            lr = lr_schedule(state.step)
            new_digital, new_sliced = panther.update_split(
                grads, state.digital, state.sliced, state.step, lr, opt_cfg, rng=state.rng,
                plan=plan_t,
            )
            gnorm = panther.global_grad_norm(grads)
        new_state = TrainState(
            step=state.step + 1, digital=new_digital, sliced=new_sliced, rng=state.rng
        )
        return new_state, {"loss": loss_val, "lr": lr, "grad_norm": gnorm}

    def train_step(state: TrainState, batch):
        with _fid_scope():
            return _train_step(state, batch)

    return train_step
