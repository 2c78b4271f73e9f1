"""Distributed fidelity smoke: the sharded all-analog loop, 1-way vs 8-way.

Runs the crossbar-in-the-loop train step (finite-ADC packed MVM forward,
MᵀVM backward, fused OPA deposit) twice on 8 forced host CPU devices —
single-host and pjit-sharded over a (2 data x 4 model) mesh — and records
per-step wall time plus tokens/sec into ``BENCH_dist.json`` (the CI
distributed-smoke artifact). It also cross-checks that the two runs' first
losses agree, so the artifact doubles as an e2e equivalence smoke.

Interpretation: on a real TPU slice the 8-way column is the scaling result;
on CI's fake CPU devices all 8 "devices" share the same cores, so 8-way is
*expected to be slower* (it adds resharding work to the same silicon) — the
artifact's job there is trend tracking and proving the sharded lowering
runs end to end, not demonstrating speedup.

``BENCH_SMOKE=1`` (the CI contract): 3 timed steps on the smoke config.
"""
from __future__ import annotations

import os

# must precede the first jax import: the whole point is 8 fake devices
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import dataclasses
import json
import time

import numpy as np
import jax
import jax.numpy as jnp

SMOKE = os.environ.get("BENCH_SMOKE") == "1"
OUT_JSON = os.environ.get("BENCH_DIST_JSON", "BENCH_dist.json")


def _timed_steps(step_fn, state, batches):
    """Run compiled steps one batch at a time; returns (losses, us_per_step)
    with the compile step excluded (min-of-rest, the low-noise estimator)."""
    losses, times = [], []
    for i, b in enumerate(batches):
        t0 = time.perf_counter()
        state, m = step_fn(state, b)
        jax.block_until_ready(m["loss"])
        times.append((time.perf_counter() - t0) * 1e6)
        losses.append(float(m["loss"]))
    us = min(times[1:]) if len(times) > 1 else times[0]
    return losses, us


def main():
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import fidelity_presets, get_smoke
    from repro.data import SyntheticLMDataset
    from repro.launch.mesh import make_mesh
    from repro.optim import PantherConfig
    from repro.optim.schedules import constant
    from repro.plan import default_rules
    from repro.train.step import (batch_specs, make_train_step,
                                  train_state_init, train_state_specs)

    steps = 3 if SMOKE else 10
    B, S = 8, 32
    cfg = dataclasses.replace(get_smoke("gemma_2b"), dtype=jnp.float32)
    opt = PantherConfig(stochastic_round=False, crs_every=1 << 20)
    fid = fidelity_presets()["adc9"]
    ds = SyntheticLMDataset(cfg.vocab, seq_len=S, global_batch=B, seed=3)
    batches = [ds.batch(i) for i in range(steps)]
    tokens = B * S

    n_dev = jax.device_count()
    results = {"_meta": {
        "arch": cfg.arch_id, "steps": steps, "batch": B, "seq": S,
        "adc": "adc9", "devices": n_dev, "backend": jax.default_backend(),
        "smoke": SMOKE,
        "note": "fake CPU devices share cores: 8-way slower than 1-way is "
                "expected off-TPU; the column proves the sharded lowering, "
                "not speedup",
    }}

    # 1-way: the single-host simulator path
    state = train_state_init(cfg, opt, jax.random.PRNGKey(0))
    step1 = jax.jit(make_train_step(cfg, opt, constant(0.3),
                                    plan_rules=default_rules(opt, fidelity=fid)))
    losses1, us1 = _timed_steps(step1, state, batches)
    results["fidelity_1way"] = {
        "us_per_step": us1, "tokens_per_sec": tokens / (us1 * 1e-6),
        "losses": losses1,
    }

    # 8-way: the same loop pjit-sharded (tokens over 'data', tiles over 'model')
    if n_dev >= 8:
        mesh = make_mesh((2, 4), ("data", "model"))
        named = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                                       is_leaf=lambda x: isinstance(x, P))
        step8 = make_train_step(cfg, opt, constant(0.3), mesh=mesh,
                                global_batch=B,
                                plan_rules=default_rules(opt, fidelity=fid))
        sspecs = named(train_state_specs(cfg, opt, mesh))
        with jax.set_mesh(mesh):
            state = jax.device_put(train_state_init(cfg, opt, jax.random.PRNGKey(0)), sspecs)
            jitted = jax.jit(
                step8,
                in_shardings=(sspecs, named(batch_specs(cfg, mesh, B))),
            )
            losses8, us8 = _timed_steps(jitted, state, batches)
        results["fidelity_8way"] = {
            "us_per_step": us8, "tokens_per_sec": tokens / (us8 * 1e-6),
            "losses": losses8, "mesh": "2x4 (data, model)",
        }
        drift = abs(losses1[0] - losses8[0]) / (1 + abs(losses1[0]))
        results["_meta"]["first_loss_rel_drift"] = drift
        fail = None
        if not all(np.isfinite(losses8)):
            fail = f"8-way fidelity losses non-finite: {losses8}"
        elif drift > 1e-3:
            fail = (f"sharded fidelity step diverged from single-host at step 0: "
                    f"{losses1[0]} vs {losses8[0]} (rel {drift:.2e})")
        if fail is not None:
            results["_meta"]["equivalence_failure"] = fail
    else:
        fail = None
        print(f"only {n_dev} device(s): set "
              "XLA_FLAGS=--xla_force_host_platform_device_count=8 for the 8-way column")

    # the artifact is written (failure recorded in _meta) BEFORE the
    # tripwire raises, so a red CI run still uploads the diagnostic
    with open(OUT_JSON, "w") as f:
        json.dump(results, f, indent=2, sort_keys=True)
    for k, v in results.items():
        if k != "_meta":
            print(f"dist/{k},{v['us_per_step']:.2f},"
                  f"tokens_per_sec={v['tokens_per_sec']:.1f};lossN={v['losses'][-1]:.4f}")
    print(f"dist/json,0.00,wrote={OUT_JSON}")
    if fail is not None:
        raise SystemExit(fail)


if __name__ == "__main__":
    main()
