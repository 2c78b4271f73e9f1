"""Distributed tests on a small forced-device CPU mesh (subprocess-isolated
so the main test process keeps its single device)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.distributed import sharding as shd
from jax.sharding import PartitionSpec as P


def _run(snippet: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = "src"
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(snippet)],
        capture_output=True, text=True, env=env, cwd=os.path.dirname(os.path.dirname(__file__)),
        timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_name_rules():
    assert shd.leaf_spec("digital/embed", 2) == P("model", None)
    assert shd.leaf_spec("groups/0/attn/wq", 2) == P(None, "model")
    assert shd.leaf_spec("groups/0/attn/wo", 2) == P("model", None)
    assert shd.leaf_spec("groups/1/moe/experts_gate", 4) == P(None, "model", None, None)
    assert shd.leaf_spec("groups/0/mlp/wi_gate", 3) == P(None, None, "model")
    assert shd.leaf_spec("groups/0/ln/scale", 1) == P(None)


def test_sanitize_spec_relocates_indivisible_axis():
    class FakeMesh:
        shape = {"data": 2, "model": 4}

    # vocab 131 not divisible by 4 -> 'model' relocates to d
    assert shd.sanitize_spec(P("model", None), (131, 64), FakeMesh()) == P(None, "model")
    # nothing to do when divisible
    assert shd.sanitize_spec(P("model", None), (128, 64), FakeMesh()) == P("model", None)
    # no home -> replicate
    assert shd.sanitize_spec(P("model", None), (131, 33), FakeMesh()) == P(None, None)


def test_fsdp_spec_transform():
    assert shd.fsdp_spec(P(None, "model"), (4096, 1024), 16, n_tail=2) == P("data", "model")
    # never touches leading stack axes
    assert shd.fsdp_spec(P(None, None, "model"), (48, 4096, 1024), 16, n_tail=2) == P(None, "data", "model")
    # skips non-divisible dims
    assert shd.fsdp_spec(P(None, "model"), (33, 1024), 16, n_tail=2) == P(None, "model")


def test_cache_spec_rules():
    import jax.numpy as jnp
    import jax
    import numpy as np

    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 4, "model": 2}

    kv = jax.ShapeDtypeStruct((8, 128, 4, 64), jnp.bfloat16)
    spec = shd.cache_specs(FakeMesh(), {"k": kv}, global_batch=8)["k"]
    assert spec[0] == "data" and "model" in tuple(spec)


def test_train_step_runs_on_mesh():
    """2x4 mesh: one pjit'd PANTHER train step executes and loss is finite."""
    out = _run("""
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_smoke
        from repro.optim import PantherConfig
        from repro.optim.schedules import constant
        from repro.plan import default_rules
        from repro.launch.mesh import make_mesh
        from repro.train.step import (batch_specs, make_train_step,
                                      train_state_init, train_state_specs)
        mesh = make_mesh((2, 4), ("data", "model"))
        cfg = get_smoke("gemma_2b")
        opt = PantherConfig(stochastic_round=False)
        B, S = 4, 32
        step = make_train_step(cfg, opt, constant(1e-2), mesh=mesh, global_batch=B, fsdp=True)
        named = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                                       is_leaf=lambda x: isinstance(x, P))
        sspecs = named(train_state_specs(cfg, opt, mesh, fsdp=True))
        batch = {"inputs": jnp.ones((B, S), jnp.int32), "labels": jnp.ones((B, S), jnp.int32)}
        with jax.set_mesh(mesh):
            state = jax.device_put(train_state_init(cfg, opt, jax.random.PRNGKey(0)), sspecs)
            jitted = jax.jit(step, in_shardings=(sspecs, named(batch_specs(cfg, mesh, B))),
                             donate_argnums=0)
            state, m = jitted(state, batch)
            state, m = jitted(state, batch)
        import math
        assert math.isfinite(float(m["loss"])), float(m["loss"])
        print("LOSS_OK", float(m["loss"]))
    """)
    assert "LOSS_OK" in out


def test_sharded_loss_matches_single_device():
    """The pjit'd loss equals the single-device loss (same params/batch)."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_smoke
        from repro.distributed import sharding as shd
        from repro.launch.mesh import make_mesh
        from repro.models import lm
        cfg = get_smoke("granite_moe_1b_a400m")
        params = lm.init_params(cfg, jax.random.PRNGKey(0))
        B, S = 4, 32
        batch = {"inputs": jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab),
                 "labels": jax.random.randint(jax.random.PRNGKey(2), (B, S), 0, cfg.vocab)}
        ref = float(lm.loss_fn(cfg, params, batch, remat=False))
        mesh = make_mesh((2, 4), ("data", "model"))
        pspecs = jax.tree.map(lambda s: NamedSharding(mesh, s), shd.param_specs(params, mesh=mesh),
                              is_leaf=lambda x: isinstance(x, P))
        with jax.set_mesh(mesh):
            f = jax.jit(lambda p, b: lm.loss_fn(cfg, p, b, remat=False), in_shardings=(pspecs, None))
            got = float(f(params, batch))
        assert abs(got - ref) < 5e-3 * (1 + abs(ref)), (got, ref)
        print("MATCH", got, ref)
    """)
    assert "MATCH" in out


# ----------------------- sharded fidelity (mesh lowering) -------------------


def test_attach_fidelity_shard_dims_follows_leaf_sharding():
    """The mesh hint lands on every fidelity leaf: column-parallel weights
    (wqkv/wi_*) get shard_dim=1, row-parallel (wo) 0; plan shard hints win
    over the name rules; a model-less mesh leaves the plan untouched."""
    import jax
    from repro import plan as planlib
    from repro.configs import get_smoke
    from repro.models import lm
    from repro.models.common import FidelityConfig
    from repro.optim import PantherConfig

    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 2, "model": 4}

    cfg = get_smoke("gemma_2b")
    shapes = jax.eval_shape(lambda: lm.init_params(cfg, jax.random.PRNGKey(0)))
    rules = planlib.default_rules(PantherConfig(), fidelity=FidelityConfig()) + (
        planlib.PlanRule("*/mlp/wo", shard=(None, "model")),  # hint overrides
    )
    plan = planlib.attach_fidelity_shard_dims(
        planlib.resolve_plan(shapes, rules), FakeMesh()
    )
    by_path = {p: pl for p, pl in planlib.plan_by_path(plan).items()
               if pl.fidelity is not None}
    assert by_path, "smoke config should have fidelity leaves"
    for path, pl in by_path.items():
        want = 1 if path.endswith(("wqkv", "wi_gate", "wi_up")) else 0
        if path.endswith("/mlp/wo"):
            want = 1  # the explicit hint flipped it column-parallel
        assert pl.fidelity.shard_dim == want, (path, pl.fidelity.shard_dim)

    class NoModelMesh:
        axis_names = ("data",)
        shape = {"data": 8}

    plan2 = planlib.attach_fidelity_shard_dims(
        planlib.resolve_plan(shapes, rules), NoModelMesh()
    )
    assert all(pl.fidelity is None or pl.fidelity.shard_dim is None
               for pl in planlib.plan_by_path(plan2).values())


def test_fidelity_mesh_step_builds():
    """Regression: make_train_step with a mesh + fidelity used to raise
    NotImplementedError ('fidelity training is a (single-host) simulator
    mode'); the sharded lowering replaced it."""
    import dataclasses
    import jax, jax.numpy as jnp
    from repro.configs import fidelity_presets, get_smoke
    from repro.launch.mesh import make_mesh
    from repro.optim import PantherConfig
    from repro.optim.schedules import constant
    from repro.plan import default_rules
    from repro.train.step import make_train_step

    cfg = dataclasses.replace(get_smoke("gemma_2b"), dtype=jnp.float32)
    mesh = make_mesh((1, 1), ("data", "model"))
    opt = PantherConfig(stochastic_round=False)
    step = make_train_step(cfg, opt, constant(0.1), mesh=mesh, global_batch=4,
                           plan_rules=default_rules(
                               opt, fidelity=fidelity_presets()["adc9"]))
    assert callable(step)


def test_sharded_fidelity_read_matches_single_host():
    """Engine-level equivalence on a 2x4 mesh: the shard_map lowering
    (tokens over 'data', crossbar tile blocks over 'model', contraction
    partials psum-reduced) is bit-identical to the single-host batched entry
    at adc_bits=None (every sum exact in f32) and reassociation-close at
    finite ADC — for both the MVM and the MᵀVM read, at every shard_dim."""
    out = _run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import DEFAULT_SPEC, slice_weights
        from repro.kernels.sliced_mvm import mvm_sliced_batched, mvm_sliced_sharded
        from repro.launch.mesh import make_mesh
        rng = np.random.default_rng(0)
        M = N = 512  # 4-way model shards hold exactly one 128-row tile each
        q = jnp.asarray(rng.integers(-256, 257, size=(M, N)), jnp.int32)
        planes = slice_weights(q, DEFAULT_SPEC)
        mesh = make_mesh((2, 4), ("data", "model"))
        for transpose in (False, True):
            contract = N if transpose else M
            x = jnp.asarray(rng.integers(-100, 101, size=(3, 5, contract)), jnp.int32)
            for adc in (None, 9):
                ref = np.asarray(mvm_sliced_batched(
                    planes, x, DEFAULT_SPEC, adc_bits=adc, transpose=transpose))
                for sd in (None, 0, 1):
                    got = np.asarray(jax.jit(lambda xx: mvm_sliced_sharded(
                        planes, xx, DEFAULT_SPEC, mesh=mesh, data_axes=("data",),
                        model_axis="model", shard_dim=sd, adc_bits=adc,
                        transpose=transpose))(x))
                    if adc is None:
                        np.testing.assert_array_equal(got, ref)
                    else:
                        np.testing.assert_allclose(got, ref, rtol=1e-6)
        print("ENGINE_OK")
    """)
    assert "ENGINE_OK" in out


def test_sharded_fidelity_train_step_matches_single_host():
    """The full crossbar-in-the-loop train step, pjit-sharded over 8 devices,
    tracks the single-host fidelity step: ideal-ADC losses agree to f32
    reassociation noise over two steps; a finite-ADC setting runs sharded
    end to end with finite metrics."""
    out = _run("""
        import dataclasses
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import fidelity_presets, get_smoke
        from repro.launch.mesh import make_mesh
        from repro.optim import PantherConfig
        from repro.optim.schedules import constant
        from repro.plan import default_rules
        from repro.train.step import (batch_specs, make_train_step,
                                      train_state_init, train_state_specs)
        cfg = dataclasses.replace(get_smoke("gemma_2b"), dtype=jnp.float32)
        opt = PantherConfig(stochastic_round=False, crs_every=1000)
        B, S = 8, 16
        batch = {"inputs": jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab),
                 "labels": jax.random.randint(jax.random.PRNGKey(2), (B, S), 0, cfg.vocab)}
        fid = fidelity_presets()["ideal"]
        s0 = train_state_init(cfg, opt, jax.random.PRNGKey(0))
        step1 = jax.jit(make_train_step(cfg, opt, constant(0.3),
                                         plan_rules=default_rules(opt, fidelity=fid)))
        s1, ma = step1(s0, batch)
        s1, mb = step1(s1, batch)
        mesh = make_mesh((2, 4), ("data", "model"))
        named = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                                       is_leaf=lambda x: isinstance(x, P))
        sspecs = named(train_state_specs(cfg, opt, mesh))
        with jax.set_mesh(mesh):
            st = jax.device_put(train_state_init(cfg, opt, jax.random.PRNGKey(0)), sspecs)
            jitted = jax.jit(
                make_train_step(cfg, opt, constant(0.3), mesh=mesh, global_batch=B,
                                plan_rules=default_rules(opt, fidelity=fid)),
                in_shardings=(sspecs, named(batch_specs(cfg, mesh, B))))
            st, na = jitted(st, batch)
            st, nb = jitted(st, batch)
        for m, n, tol in ((ma, na, 1e-3), (mb, nb, 5e-3)):
            d = abs(float(m["loss"]) - float(n["loss"]))
            assert d < tol * (1 + abs(float(m["loss"]))), (d, float(m["loss"]), float(n["loss"]))
        # finite ADC: runs sharded end to end, planes update
        with jax.set_mesh(mesh):
            st = jax.device_put(train_state_init(cfg, opt, jax.random.PRNGKey(0)), sspecs)
            jitted6 = jax.jit(
                make_train_step(cfg, opt, constant(0.3), mesh=mesh, global_batch=B,
                                plan_rules=default_rules(
                                    opt, fidelity=fidelity_presets()["adc6"])),
                in_shardings=(sspecs, named(batch_specs(cfg, mesh, B))))
            st6, m6 = jitted6(st, batch)
        assert np.isfinite(float(m6["loss"])) and np.isfinite(float(m6["grad_norm"]))
        changed = any(
            (np.asarray(a.planes) != np.asarray(b.planes)).any()
            for a, b in zip(
                jax.tree.leaves(st.sliced, is_leaf=lambda x: hasattr(x, "planes")),
                jax.tree.leaves(st6.sliced, is_leaf=lambda x: hasattr(x, "planes")),
            ) if hasattr(a, "planes"))
        assert changed
        print("STEP_OK", float(ma["loss"]), float(na["loss"]))
    """)
    assert "STEP_OK" in out


def test_compressed_psum_shardmap():
    """Quantized gradient all-reduce: unbiased and near-exact at 16 bits."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.distributed.collectives import compressed_psum
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("data",))
        x = jax.random.normal(jax.random.PRNGKey(0), (8, 64), jnp.float32)
        f = jax.shard_map(lambda g: compressed_psum(g, "data"), mesh=mesh,
                          in_specs=P("data", None), out_specs=P(None))
        got = np.asarray(f(x))[0] if False else np.asarray(f(x))
        ref = np.asarray(x.sum(0))
        err = np.abs(got - ref).max() / (np.abs(ref).max() + 1e-9)
        assert err < 2e-3, err
        print("PSUM_OK", err)
    """)
    assert "PSUM_OK" in out
