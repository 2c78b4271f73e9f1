#!/usr/bin/env python3
"""Chip smoke run: the PANTHER train step, the analog-read (finite-ADC)
path and the serving engine on one TPU chip, at phi4-mini published widths.

    python3 chip_smoke.py                # one chip: four phases, below
    python3 chip_smoke.py --four-chips   # 2x2 (data, model) mesh only

Model: ``phi4_mini_3p8b`` at its published widths (d_model 3072, 24 query and
8 KV heads of 128, d_ff 8192, the full 200,064-token vocabulary, tied
embedding), depth cut to ``LAYERS`` = 2. Weights and data are random, drawn
from ``--seed``. Every phase calls the library's own entry points:

1. train — ``train.step.make_train_step`` with the PANTHER optimizer
   (operand gradients, fused OPA deposit, CRS every 2 steps); the step-0 loss
   is near ln(vocab); one checkpoint is saved under ``--out`` and restored
   bit for bit.
2. analog read — the same step under the ``adc9`` fidelity plan: forward
   and backward reads go through the fused sliced-MVM kernel; the loss lies
   within 1% of the lossless loss of the same planes.
3. serve — ``serve.engine.Engine`` + ``serve.scheduler.run_trace`` answer a
   few requests from the trained planes; every greedy token is the argmax of
   a full forward pass over the served sequence, up to bf16 noise.
4. kernel = reference — one fused OPA deposit at a real-width leaf equals the
   jnp reference bit for bit; ``adc9`` and ideal fused reads agree with
   ``mvm_sliced_fused_ref``; both sides run on the chip.

Each compiled step must hold the repo's Pallas kernels as ``tpu_custom_call``
and none may run in interpret mode. ``--four-chips`` runs only the phase-2
step on a 2x2 mesh (FSDP, sharded reads) beside the same step on one device:
with an ideal ADC the losses of two steps agree (the second reads the planes
the first wrote); the adc9 step runs sharded and writes every plane leaf, and
its loss is printed beside the one-device loss. Times printed are smoke
timings of one run, not benchmarks. The script exits non-zero when JAX finds no TPU or any
check fails; its last stdout line is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import pathlib
import shutil
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent

TRAIN_KERNELS = ("panther_opa_fused", "panther_opa_deposit", "panther_crs")
# the phase-4 leaf: phi4-mini's wi_gate [d_model, d_ff] and one token tile
KERNEL_LEAF = (3072, 8192, 512)
FUSED_READ_KERNELS = ("panther_mvm_fused_db", "panther_mvm_fused_t_db")
VOCAB_SHARE = 66688  # one third of phi4-mini's 200,064-token vocabulary
# sized with compiled.memory_analysis(): on a v5e the train step takes
# 11.5 GB of the chip's 16 GB, the adc9 step 12.5 GB
LAYERS = 2
STEPS = 3
TRAIN_BATCH = (4, 512)  # (batch, seq)
FID_BATCH = (2, 256)  # the analog read computes 120 column currents per MAC
LR = 1e-2


def log(msg):
    print(msg, flush=True)


def check(ok, what):
    """A smoke check: raise (and so exit non-zero) when it fails."""
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded fidelity step on a 2x2 mesh vs one device")
    ap.add_argument("--seed", type=int, default=0, help="seed of weights and data")
    ap.add_argument("--out", default=str(ROOT / "chip_smoke_out"),
                    help="directory for the phase-1 checkpoint (removed after the restore check)")
    return ap.parse_args(argv)


def model_config(layers: int):
    from repro import configs

    cfg = configs.get("phi4_mini_3p8b")
    return dataclasses.replace(cfg, n_layers=layers, pattern=(("dense", layers),))


def compile_checked(jitted, args, required):
    """Trace, lower and compile ``jitted`` for ``args``; check that every
    required kernel is a ``tpu_custom_call`` and no pallas_call runs in
    interpret mode. Returns the compiled executable."""
    from repro.kernels.common import pallas_calls, tpu_kernels_in_hlo

    t0 = time.perf_counter()
    traced = jitted.trace(*args)
    calls = pallas_calls(traced.jaxpr)
    interpreted = sorted({name for name, interp in calls if interp})
    check(not interpreted, f"kernels traced in interpret mode: {interpreted}")
    compiled = traced.lower().compile()
    secs = time.perf_counter() - t0
    kernels = tpu_kernels_in_hlo(compiled.as_text())
    missing = [k for k in required if not kernels.get(k)]
    check(not missing, f"kernels missing from the compiled HLO: {missing}; found {kernels}")
    ma = compiled.memory_analysis()
    log(f"  compiled in {secs:.1f} s; tpu_custom_call kernels {kernels}")
    if ma is not None:
        log(f"  memory_analysis: arguments {ma.argument_size_in_bytes / 1e9:.2f} GB, "
            f"temp {ma.temp_size_in_bytes / 1e9:.2f} GB, "
            f"output {ma.output_size_in_bytes / 1e9:.2f} GB (aliased "
            f"{ma.alias_size_in_bytes / 1e9:.2f} GB)")
    return compiled


def run_steps(compiled, state, batches, label):
    import jax

    losses = []
    for i, batch in enumerate(batches):
        t0 = time.perf_counter()
        state, m = compiled(state, batch)
        loss = float(jax.block_until_ready(m["loss"]))
        gnorm = float(m["grad_norm"])
        log(f"  {label} step {i}: loss {loss:.5f} grad_norm {gnorm:.4g} "
            f"({time.perf_counter() - t0:.3f} s, smoke timing)")
        check(math.isfinite(loss) and math.isfinite(gnorm), f"non-finite loss {loss} or grad norm {gnorm}")
        losses.append(loss)
    return state, losses


def trees_equal(a, b) -> bool:
    import jax
    import jax.numpy as jnp

    same = jax.jit(lambda x, y: jnp.all(x == y))
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        x.shape == y.shape and x.dtype == y.dtype and bool(same(x, y)) for x, y in zip(la, lb)
    )


def phase_train(cfg, opt, args):
    import jax

    from repro.checkpoint import restore_latest, save_checkpoint
    from repro.data import SyntheticLMDataset
    from repro.optim.schedules import constant
    from repro.train.step import make_train_step, train_state_init

    B, S = TRAIN_BATCH
    log(f"phase 1 train: batch {B} x seq {S}, crs_every {opt.crs_every}")
    ds = SyntheticLMDataset(cfg.vocab, S, B, seed=args.seed)
    batches = [ds.batch(i) for i in range(STEPS)]
    # jitted: op by op, the embedding's int32 slicing temporaries exceed the
    # chip; compiled, the init takes 6.5 GB of state + 2.5 GB of temp
    state = jax.jit(lambda: train_state_init(cfg, opt, jax.random.PRNGKey(args.seed)))()
    step = jax.jit(make_train_step(cfg, opt, constant(LR)), donate_argnums=0)
    compiled = compile_checked(step, (state, batches[0]), TRAIN_KERNELS)
    state, losses = run_steps(compiled, state, batches, "train")
    ln_v = math.log(cfg.vocab)
    check(abs(losses[0] - ln_v) < 1.0, f"step-0 loss {losses[0]} far from ln(vocab) {ln_v}")
    log(f"  step-0 loss {losses[0]:.5f} vs ln(vocab) {ln_v:.5f}")

    ckpt_dir = pathlib.Path(args.out) / "ckpt"
    t0 = time.perf_counter()
    save_checkpoint(str(ckpt_dir), STEPS, state)
    restored, rstep = restore_latest(str(ckpt_dir), state)
    check(rstep == STEPS, f"restored step {rstep}, saved {STEPS}")
    check(trees_equal(state, restored), "restored checkpoint differs from the saved state")
    shutil.rmtree(ckpt_dir)
    log(f"  checkpoint saved and restored bit for bit ({time.perf_counter() - t0:.1f} s)")
    return restored


def fidelity_rules(opt, preset="adc9"):
    from repro import configs
    from repro import plan as planlib

    fid = dataclasses.replace(configs.fidelity_presets()[preset], spec=opt.spec)
    return planlib.default_rules(opt, fidelity=fid)


def phase_analog_read(cfg, opt, args, state):
    import jax

    from repro.data import SyntheticLMDataset
    from repro.models import lm
    from repro.optim import panther
    from repro.optim.schedules import constant
    from repro.train.step import make_train_step

    B, S = FID_BATCH
    log(f"phase 2 analog read (adc9): batch {B} x seq {S}")
    ds = SyntheticLMDataset(cfg.vocab, S, B, seed=args.seed + 1)
    batches = [ds.batch(i) for i in range(2)]
    lossless = jax.jit(lambda d, s, b: lm.loss_fn(
        cfg, panther.materialize_split(d, s, opt), b))
    ref_loss = float(lossless(state.digital, state.sliced, batches[0]))
    step = jax.jit(make_train_step(cfg, opt, constant(LR), plan_rules=fidelity_rules(opt)),
                   donate_argnums=0)
    compiled = compile_checked(step, (state, batches[0]), TRAIN_KERNELS + FUSED_READ_KERNELS)
    state, losses = run_steps(compiled, state, batches, "adc9")
    log(f"  adc9 loss {losses[0]:.5f} vs lossless loss {ref_loss:.5f} of the same planes")
    # the 9-bit column reads perturb every logit (+0.061 nats measured on a
    # v5e at these widths); a misread tile or scale moves the loss by whole
    # nats. Bit-level agreement of the read is phase 4's check.
    check(abs(losses[0] - ref_loss) < 0.01 * ref_loss,
          f"adc9 read loss {losses[0]} departs from the lossless {ref_loss}")
    return state


def served_params(opt, state):
    """The lossless serving tree: the trained planes, dequantized."""
    import jax

    from repro.optim import panther

    return jax.jit(lambda d, s: panther.materialize_split(d, s, opt))(state.digital, state.sliced)


def phase_serve(cfg, args, params):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import lm
    from repro.serve import scheduler as sch
    from repro.serve import trace as tracelib
    from repro.serve.engine import Engine

    log("phase 3 serve: engine + scheduler from the trained planes")
    trace = tracelib.synth_trace(seed=args.seed, n_requests=4, rate=1e4, prompt_lens=(8, 24),
                                 out_lens=(8, 8), vocab=cfg.vocab)
    eng = Engine(cfg, params, n_slots=4, max_seq=64, page=16, chunk_size=16)
    t0 = time.perf_counter()
    res = sch.run_trace({"default": eng}, trace, policy="continuous")
    log(f"  served {len(res['requests'])} requests in {time.perf_counter() - t0:.1f} s wall "
        f"(compiles included, smoke timing)")
    check(len(res["requests"]) == len(trace), f"{len(res['requests'])} of {len(trace)} requests served")

    # reference: every greedy token is the argmax of one full forward pass
    # over the served sequence (teacher-forced), up to bf16 noise
    forward = jax.jit(lambda p, x: lm.forward(cfg, p, x, remat=False)[0])
    by_rid = {r.rid: r for r in trace}
    exact = total = 0
    for done in res["requests"]:
        req = by_rid[done.rid]
        check(len(done.tokens) == req.out_len, f"request {done.rid}: {len(done.tokens)} of {req.out_len} tokens")
        seq = np.concatenate([req.tokens, np.asarray(done.tokens[:-1], np.int32)])
        logits = np.asarray(forward(params, jnp.asarray(seq)[None]), np.float32)[0]
        check(np.isfinite(logits).all(), "non-finite reference logits")
        L = len(req.tokens)
        for i, tok in enumerate(done.tokens):
            row = logits[L - 1 + i]
            gap = float(row.max() - row[tok])
            check(gap <= 2e-2 * (1.0 + float(np.abs(row).max())),
                  f"request {done.rid} token {i}: {gap} below the reference argmax logit")
            exact += int(row.argmax() == tok)
            total += 1
    log(f"  {exact}/{total} served tokens are the reference argmax exactly; "
        f"the rest lie within bf16 noise of it")


def phase_kernels(args):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import DEFAULT_SPEC, slice_weights
    from repro.core.fixed_point import choose_frac_bits
    from repro.kernels.sliced_mvm import mvm_sliced_fused
    from repro.kernels.sliced_mvm.ref import mvm_sliced_fused_ref
    from repro.kernels.sliced_opa import opa_fused_update

    spec = DEFAULT_SPEC
    M, N, T = KERNEL_LEAF
    log(f"phase 4 kernel = reference on the chip: [{M}, {N}] leaf, {T} tokens")
    k = jax.random.split(jax.random.PRNGKey(args.seed + 4), 6)
    q = jax.random.randint(k[0], (M, N), -(2**26), 2**26, jnp.int32)
    planes = slice_weights(q, spec)
    # operands on an exact dyadic grid: 12-bit activations (more than one
    # bf16 pass holds) and 4-bit cotangents, every partial sum < 2^24 ulps —
    # the f32 contraction is exact in any order, so kernel and reference
    # must agree bit for bit unless one side loses precision
    x = jax.random.randint(k[1], (T, M), -2048, 2048).astype(jnp.float32) * 2.0**-11
    dh = jax.random.randint(k[2], (T, N), -8, 9).astype(jnp.float32) * 2.0**-3
    def deposit(use_kernel):
        return jax.jit(lambda p, a, d: opa_fused_update(
            p, a, d, jnp.float32(0.05), jnp.int32(16), spec, stochastic=True, key=k[3],
            rng_mode="counter", use_kernel=use_kernel))(planes, x, dh)

    got, want = deposit(True), deposit(False)
    diff = int(jnp.sum(got != want))
    moved = int(jnp.sum(got != planes))
    log(f"  opa_fused_update: {moved} digits deposited, {diff} differ from the reference")
    check(moved > 0 and diff == 0, f"deposit: {moved} digits moved, {diff} differ")

    xs = jax.random.normal(k[4], (64, M), jnp.float32)
    xt = jax.random.normal(k[5], (64, N), jnp.float32)
    for adc in (9, None):
        for transpose, xx in ((False, xs), (True, xt)):
            f = choose_frac_bits(xx, word_bits=16, margin_bits=1, clip_to_word=False)
            out = mvm_sliced_fused(planes, xx, f, spec, adc_bits=adc, transpose=transpose,
                                   use_kernel=True)
            ref = jax.jit(lambda p, a, fb: mvm_sliced_fused_ref(
                p, a, fb, spec, 16, adc, transpose=transpose))(planes, xx, f)
            err = float(jnp.abs(out - ref).max())
            tol = 1e-3 * (1.0 + float(jnp.abs(ref).max()))
            log(f"  mvm_sliced_fused adc={adc} transpose={transpose}: max |kernel - ref| "
                f"{err:.4g} (tolerance {tol:.4g})")
            check(np.isfinite(err) and err <= tol, f"read error {err} above {tol}")


def sharded_vs_one_device(cfg, opt, args, preset, steps):
    """``steps`` steps of the fidelity-plan train step on one device and on a
    2x2 (data, model) mesh with FSDP, from the same seed and batch. Checks
    that every plane leaf moved on the mesh; returns the losses of each."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.data import SyntheticLMDataset
    from repro.launch.mesh import make_mesh
    from repro.optim.schedules import constant
    from repro.train.step import batch_specs, make_train_step, train_state_init, train_state_specs

    devices = jax.devices()
    B, S = FID_BATCH[0] * 2, FID_BATCH[1]
    batches = [SyntheticLMDataset(cfg.vocab, S, B, seed=args.seed + 1).batch(0)] * steps
    rules = fidelity_rules(opt, preset)
    init = lambda: train_state_init(cfg, opt, jax.random.PRNGKey(args.seed))

    log(f"  {preset}, one device:")
    with jax.default_device(devices[0]):
        state = jax.jit(init)()
        step = jax.jit(make_train_step(cfg, opt, constant(LR), plan_rules=rules),
                       donate_argnums=0)
        compiled = compile_checked(step, (state, batches[0]), TRAIN_KERNELS + FUSED_READ_KERNELS)
        state, ref_losses = run_steps(compiled, state, batches, "one device")
        del state, step, compiled

    log(f"  {preset}, 2x2 mesh:")
    mesh = make_mesh((2, 2), ("data", "model"), devices=devices)
    named = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                                   is_leaf=lambda x: isinstance(x, P))
    sspecs = named(train_state_specs(cfg, opt, mesh, fsdp=True))
    with jax.set_mesh(mesh):
        state = jax.jit(init, out_shardings=sspecs)()
        before = jax.tree.map(jnp.copy, state.sliced)
        step = jax.jit(make_train_step(cfg, opt, constant(LR), mesh=mesh, global_batch=B,
                                       fsdp=True, plan_rules=rules),
                       in_shardings=(sspecs, named(batch_specs(cfg, mesh, B))),
                       donate_argnums=0)
        # under a mesh only the reads run Pallas kernels (in a shard_map);
        # the update runs the optimizer's jnp references (train.step)
        compiled = compile_checked(step, (state, batches[0]), FUSED_READ_KERNELS)
        state, losses = run_steps(compiled, state, batches, "2x2 mesh")
        is_st = lambda x: hasattr(x, "planes")
        moved = [bool(jnp.any(a.planes != b.planes)) for a, b in zip(
            jax.tree.leaves(before, is_leaf=is_st), jax.tree.leaves(state.sliced, is_leaf=is_st))]
        check(moved and all(moved), "a plane leaf did not move on the mesh")
        del state, step, compiled, before
    log(f"  {preset}: losses one device {ref_losses}, mesh {losses}")
    return ref_losses, losses


def phase_four_chips(cfg, opt, args):
    import jax
    import jax.numpy as jnp

    n = len(jax.devices())
    check(n == 4, f"--four-chips needs 4 devices, JAX found {n}")
    # f32 activations: in bf16 the mesh program's reassociated sums round the
    # activations differently and the two runs part by ~1% of an update. The
    # one-device f32 step of the full vocabulary needs ~17 GB, so the
    # comparison holds one third of it (a multiple of 128 rows)
    cfg = dataclasses.replace(cfg, dtype=jnp.float32, vocab=VOCAB_SHARE)
    log(f"four chips: fidelity step (f32 activations), vocab held {cfg.vocab} of 200064 "
        f"(one third: the one-device f32 reference of the full vocabulary exceeds 16 GB), "
        f"batch {FID_BATCH[0] * 2} x seq {FID_BATCH[1]}, one device vs a 2x2 (data, "
        f"model) mesh with FSDP")
    # ideal ADC, held to tests/test_distributed.py::
    # test_sharded_fidelity_train_step_matches_single_host: the second loss
    # reads the planes the first step wrote
    ref, got = sharded_vs_one_device(cfg, opt, args, "ideal", steps=2)
    for r, g, tol in zip(ref, got, (1e-3, 5e-3)):
        check(abs(r - g) <= tol * (1.0 + abs(r)), f"ideal losses {ref} vs {got}")
    # adc9: DAC and ADC codes are discontinuous, so the last-bit differences
    # of the mesh's reassociated sums flip codes, and each layer passes whole
    # ADC steps of difference on to the next: the sharded adc9 step must run
    # and write planes, and its loss is reported next to the one-device loss
    ref, got = sharded_vs_one_device(cfg, opt, args, "adc9", steps=1)
    log(f"  adc9 loss, mesh - one device: {got[0] - ref[0]:.6g}")


def main(argv=None):
    args = parse_args(argv)
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found platform {dev.platform!r}")
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache
    from repro.optim import PantherConfig

    log(f"device {dev.device_kind} x {len(jax.devices())}; compile cache {enable_compile_cache()}")
    cfg = model_config(LAYERS)
    opt = PantherConfig(crs_every=2)
    log(f"model {cfg.arch_id} at published widths: d_model {cfg.d_model}, heads "
        f"{cfg.n_heads}/{cfg.n_kv_heads} x {cfg.head_dim}, d_ff {cfg.d_ff}; layers held "
        f"{cfg.n_layers} of 32, vocab held {cfg.vocab} of 200064")
    if args.four_chips:
        phase_four_chips(cfg, opt, args)
    else:
        state = phase_train(cfg, opt, args)
        state = phase_analog_read(cfg, opt, args, state)
        params = served_params(opt, state)
        del state
        phase_serve(cfg, args, params)
        del params
        phase_kernels(args)
        stats = dev.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            log(f"peak_bytes_in_use {stats['peak_bytes_in_use'] / 1e9:.2f} GB")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
