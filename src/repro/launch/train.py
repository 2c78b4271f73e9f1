"""Training launcher: ``python -m repro.launch.train --arch gemma-2b --steps 50``.

Production features exercised here even in single-host runs:
  * PANTHER sliced-OPA optimizer (the paper's technique) with CRS schedule;
  * checkpoint/restart: atomic commits every ``--ckpt-every``, resume from
    the latest commit (crash-consistent — kill the process mid-run and
    relaunch to test); straggler-tolerant deterministic data (step-indexed);
  * optional mesh (``--mesh debug``: 2x2 CPU mesh via forced host devices).
"""
from __future__ import annotations

import argparse
import os
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma-2b")
    ap.add_argument("--smoke", action="store_true", help="use the reduced config")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-2)
    ap.add_argument("--schedule", default="constant", choices=["constant", "cosine", "wsd"])
    ap.add_argument("--crs-every", type=int, default=1024)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--mesh", default="none", choices=["none", "debug"])
    ap.add_argument("--fidelity", default="none",
                    choices=["none", "ideal", "adc9", "adc6", "adc6_fwd", "adc6_bwd"],
                    help="crossbar-in-the-loop preset: train through the finite-ADC "
                         "sliced-MVM/MᵀVM engine (works with --mesh: the reads run "
                         "shard_map-sharded over the debug mesh)")
    ap.add_argument("--log-every", type=int, default=5)
    args = ap.parse_args()

    if args.mesh == "debug":
        os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

    import jax

    from repro import configs
    from repro.checkpoint import CheckpointManager
    from repro.launch.compile_cache import enable_compile_cache
    from repro.data import SyntheticLMDataset
    from repro.optim import PantherConfig
    from repro.optim.schedules import constant, cosine, wsd
    from repro.train.step import TrainState, make_train_step, train_state_init

    enable_compile_cache()
    cfg = configs.get_smoke(args.arch) if args.smoke else configs.get(args.arch)
    sched = {
        "constant": lambda: constant(args.lr),
        "cosine": lambda: cosine(args.lr, warmup=max(args.steps // 20, 1), total=args.steps),
        "wsd": lambda: wsd(args.lr, warmup=max(args.steps // 20, 1),
                           stable=int(args.steps * 0.7), decay=max(int(args.steps * 0.25), 1)),
    }[args.schedule]()
    opt_cfg = PantherConfig(crs_every=args.crs_every, stochastic_round=True)

    mesh = None
    if args.mesh == "debug":
        from repro.launch.mesh import make_debug_mesh

        mesh = make_debug_mesh()

    rules = None
    if args.fidelity != "none":
        import dataclasses

        from repro import plan as planlib

        # the engine must read the planes the optimizer writes
        fid = dataclasses.replace(configs.fidelity_presets()[args.fidelity],
                                  spec=opt_cfg.spec)
        rules = planlib.default_rules(opt_cfg, fidelity=fid)

    ds = SyntheticLMDataset(cfg.vocab, args.seq, args.batch)
    step_fn = make_train_step(cfg, opt_cfg, sched, mesh=mesh,
                              global_batch=args.batch if mesh else None,
                              plan_rules=rules)
    # jitted: op by op, slicing a large embedding keeps every int32
    # temporary alive at once
    state = jax.jit(lambda: train_state_init(cfg, opt_cfg, jax.random.PRNGKey(0)))()

    ckpt = CheckpointManager(args.ckpt_dir, every=args.ckpt_every) if args.ckpt_dir else None
    start = 0
    if ckpt:
        restored, rstep = ckpt.restore(state)
        if restored is not None:
            state, start = restored, rstep
            print(f"resumed from step {rstep}")

    jitted = jax.jit(step_fn, donate_argnums=0)
    t0 = time.time()
    for step in range(start, args.steps):
        batch = ds.batch(step)
        state, metrics = jitted(state, batch)
        if step % args.log_every == 0 or step == args.steps - 1:
            print(f"step {step:5d} loss {float(metrics['loss']):.4f} "
                  f"lr {float(metrics['lr']):.2e} gnorm {float(metrics['grad_norm']):.3f} "
                  f"({(time.time() - t0):.1f}s)", flush=True)
        if ckpt:
            ckpt.maybe_save(step, state)
    if ckpt:
        from repro.checkpoint import save_checkpoint

        save_checkpoint(ckpt.directory, args.steps - 1, state)
    print("done")


if __name__ == "__main__":
    main()
