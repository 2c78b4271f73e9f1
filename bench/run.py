#!/usr/bin/env python3
"""One run of one benchmark cell on the chip:

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``'s ``workloads``) names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``); its limits are in
``bench/limits/<cell>.json`` and each per-layer metric is read by
``bench/metrics/<metric>.py``. The configuration file's ``config`` object
builds the program's ``LMConfig`` (``program_config``), and its
``reference`` names the plain reference module under ``bench/``
(``load_reference``; ``refmodel`` where it names none).

Set-up makes the weights from the seed on the device and hands them to the
program's ``panther.init_split``, compiles the program's jitted, donated
``train.step.make_train_step`` (from the persistent compilation cache in the
checkout), and drives that one compiled step through the cell's first
``CHECK_STEPS`` steps, reading the state each leaves. The window then
dispatches steps back to back, each on a batch made inside the window, with
at most ``LOOKAHEAD`` steps in flight; at the deadline it stops dispatching
and waits for the last step. ``train_tokens_per_s`` is the tokens of every
step dispatched over the whole elapsed time. After the window the program's
state is freed and the plain reference repeats the first steps;
``bench/compare.py`` decides ``correct``.

With ``--trace 1`` the window runs under the profiler and the line carries
the cell's per-layer metrics instead of its end-to-end ones. The last stdout
line is the result as JSON; the numbers compared are the last stderr lines.
The run fails, and prints no result, without a TPU, with fewer chips than the
cell asks for, on a device missing from ``bench/peaks.json``, or without the
program's sources beside the benchmark.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import dataclasses  # noqa: E402
import functools  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402
import typing  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
LOOKAHEAD = 2  # steps in flight before the host waits for the oldest
CHECK_STEPS = 3  # steps that set-up drives and the reference repeats
DEFAULT_REFERENCE = "refmodel"  # bench/refmodel.py: the dense decoder


def log(msg):
    print(msg, flush=True)


def load_cell(name: str, root: pathlib.Path = ROOT) -> dict:
    """The cell ``name`` with its configuration, traffic, limits and metrics."""
    bm = json.loads((root / "BENCHMARK.json").read_text())
    (wl,) = [w for w in bm["workloads"] if w["name"] == name] or [None]
    if wl is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    (conf,) = [c for c in bm["configs"] if c["name"] == wl["config"]]
    conf_file = json.loads((root / conf["file"]).read_text())
    limits = root / "bench" / "limits" / f"{name}.json"

    def mine(metrics):
        return [m for m in metrics if name in m.get("workloads", [name])]

    return {
        "name": name,
        "chips": wl["chips"],
        "config_name": conf["name"],
        "config": conf_file["config"],
        "reference": conf_file.get("reference", DEFAULT_REFERENCE),
        "traffic": json.loads((root / "bench" / "traffic" / f"{wl['traffic']}.json").read_text()),
        "limits": json.loads(limits.read_text())["limits"] if limits.exists() else {},
        "end_to_end": mine(bm["end_to_end"]),
        "per_layer": mine(bm["per_layer"]),
    }


def load_reader(metric: str):
    path = BENCH / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location("bench_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def path_str(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", getattr(k, "name", k)))) for k in path)


def load_reference(cell: dict):
    """The plain reference module that the cell's configuration file names
    (``"reference"``: a module under ``bench/``), ``bench/refmodel.py`` where
    it names none. ``refmodel``'s docstring lists what a reference provides."""
    import importlib

    name = cell.get("reference", DEFAULT_REFERENCE)
    if not re.fullmatch(r"[A-Za-z_]\w*", name):
        raise ValueError(f"reference {name!r} is not the name of a module under bench/")
    return importlib.import_module(f"bench.{name}")


def _from_json(tp, value, where: str):
    """A JSON value as the program's field type ``tp``: an object as the
    field's dataclass (a key it does not have is an error), a list as a
    tuple, anything else as it is."""
    for t in (tp, *typing.get_args(tp)):
        if dataclasses.is_dataclass(t) and isinstance(value, dict):
            hints = typing.get_type_hints(t)
            unknown = sorted(set(value) - set(hints))
            if unknown:
                raise ValueError(f"{where}: {t.__name__} has no field {', '.join(unknown)}")
            return t(**{k: _from_json(hints[k], v, f"{where}.{k}") for k, v in value.items()})
    if isinstance(value, list):
        return tuple(_from_json(None, v, where) for v in value)
    return value


def program_config(cell: dict):
    """The program's ``LMConfig`` from the configuration file's ``config``:
    every key that is a field of ``LMConfig``, nested objects (``mla``,
    ``moe``, ...) as the field's dataclass, ``pattern`` as ``[[block, count],
    ...]`` (``[[block, n_layers]]`` where it is absent) and ``dtype`` through
    ``jnp.dtype``. Other keys (``block``, and what only the reference reads)
    are the reference's."""
    import jax.numpy as jnp
    from repro.models.common import LMConfig

    conf = cell["config"]
    hints = typing.get_type_hints(LMConfig)
    kw = {k: _from_json(hints[k], v, k) for k, v in conf.items() if k in hints and k not in ("arch_id", "dtype")}
    if "pattern" not in kw:
        kw["pattern"] = ((conf["block"], conf["n_layers"]),)
    return LMConfig(arch_id=cell["config_name"], dtype=jnp.dtype(conf["dtype"]), **kw)


def program_objects(cell: dict):
    """The program's config, optimizer config and plan rules."""
    from repro import plan as planlib
    from repro.core import SliceSpec
    from repro.models.common import FidelityConfig
    from repro.optim import PantherConfig

    from bench.refmodel import Numerics

    traffic = cell["traffic"]
    num = Numerics.from_traffic(traffic)
    cfg = program_config(cell)
    opt = PantherConfig(spec=SliceSpec(bits=num.slice_bits), crs_every=traffic["crs_every"],
                        margin_bits=num.margin_bits)
    rules = None
    if num.analog:
        fid = FidelityConfig(io_bits=num.io_bits, adc_bits_fwd=num.adc_bits_fwd,
                             adc_bits_bwd=num.adc_bits_bwd, margin_bits=num.dac_margin_bits,
                             spec=opt.spec)
        rules = planlib.default_rules(opt, fidelity=fid)
    return cfg, opt, rules


def run_cell(cell: dict, seed: int, seconds: float, trace: bool, peaks: dict | None) -> dict:
    """Set up, measure and check one run; returns the result object."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.optim import panther
    from repro.optim.schedules import constant
    from repro.train import step as train_step

    from bench import compare as cmp
    from bench import tokens as tok
    from bench import trace_reduce as tr
    from bench.refmodel import Numerics

    R = load_reference(cell)
    traffic = cell["traffic"]
    m = R.Model.from_config(cell["config"])
    num = Numerics.from_traffic(traffic)
    B, S, lr, n_check = traffic["batch"], traffic["seq"], traffic["lr"], CHECK_STEPS
    if traffic["crs_every"] <= n_check:
        raise ValueError("a carry resolution inside the compared steps is not modelled")
    cfg, opt, rules = program_objects(cell)
    key = R.seed_key(seed)
    stream = tok.TokenStream(m.vocab, S, B, seed)
    devices = jax.devices()[: cell["chips"]]
    log(f"cell {cell['name']}: {R.describe(m)}; batch {B} x {S}, lr {lr}, "
        f"adc {num.adc_bits_fwd}/{num.adc_bits_bwd}; device {devices[0].device_kind} x {len(devices)}")

    def batch(i):
        x, y = stream.batch(i)
        return {"inputs": jnp.asarray(x), "labels": jnp.asarray(y)}

    # ---- set-up: state from the seed, the compiled step, the first steps
    def make_state(k):
        params = R.nest(R.gen_params(k, m))
        digital, sliced = panther.init_split(params, opt)
        return train_step.TrainState(step=jnp.zeros((), jnp.int32), digital=digital,
                                     sliced=sliced, rng=jax.random.fold_in(k, 1))

    state = jax.jit(make_state)(key)
    jax.clear_caches()  # a loaded TPU program keeps its temporaries reserved
    step_fn = jax.jit(train_step.make_train_step(cfg, opt, constant(lr), plan_rules=rules),
                      donate_argnums=0)
    t0 = time.perf_counter()
    compiled = step_fn.lower(state, batch(0)).compile()
    hlo = compiled.as_text()
    calls = tr.kernel_calls(hlo)
    counts = collections.Counter(tr.family(n) for n in calls)
    log(f"step compiled in {time.perf_counter() - t0:.1f} s; kernels {dict(sorted(counts.items()))}")
    ma = compiled.memory_analysis()
    if ma is not None:
        log(f"memory_analysis: arguments {ma.argument_size_in_bytes} B, temp {ma.temp_size_in_bytes} B, "
            f"output {ma.output_size_in_bytes} B, aliased {ma.alias_size_in_bytes} B")

    reader = R.Reader(m, num)
    readings = functools.partial(reader, key)

    def program_flat(st) -> tuple:
        is_st = lambda x: isinstance(x, panther.SlicedTensor)
        planes, fbits = {}, {}
        for path, leaf in jax.tree_util.tree_flatten_with_path(st.sliced, is_leaf=is_st)[0]:
            planes[path_str(path)], fbits[path_str(path)] = leaf.planes, int(leaf.frac_bits)
        digital = {path_str(p): v for p, v in jax.tree_util.tree_flatten_with_path(st.digital)[0]}
        return planes, fbits, digital

    prog_planes, prog_fbits, prog_digital = program_flat(state)
    expected = set(reader.norm)
    if set(prog_planes) != expected:
        raise RuntimeError(f"the program maps {sorted(prog_planes)} to planes, the reference {sorted(expected)}")
    del prog_planes, prog_digital
    losses = []
    for i in range(n_check):
        state, met = compiled(state, batch(i))
        losses.append(float(met["loss"]))
        if i == 0:
            change1, _ = readings(*program_flat(state)[::2])
    change, _ = readings(*program_flat(state)[::2])
    prog = {"loss": losses, "change1": change1, "change": change, "frac_bits": prog_fbits}
    log(f"program losses {losses}")

    # ---- the window
    profile_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        jax.profiler.start_trace(profile_dir)
    window_losses, inflight = [], collections.deque()
    i = n_check
    t_window = time.perf_counter()
    deadline = t_window + seconds
    with jax.profiler.TraceAnnotation("bench.window"):
        while time.perf_counter() < deadline:
            with jax.profiler.TraceAnnotation("bench.batch"):
                b = batch(i)
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                state, met = compiled(state, b)
            window_losses.append(met["loss"])
            inflight.append(met["loss"])
            i += 1
            if len(inflight) > LOOKAHEAD:
                with jax.profiler.TraceAnnotation("bench.wait"):
                    inflight.popleft().block_until_ready()
        with jax.profiler.TraceAnnotation("bench.drain"):
            jax.block_until_ready((state, window_losses[-1]))
    elapsed = time.perf_counter() - t_window
    if trace:
        jax.profiler.stop_trace()
    setup_s = t_window - T_START
    steps = len(window_losses)
    tokens_per_s = steps * B * S / elapsed
    failed = int(np.sum(~np.isfinite(np.asarray(jnp.stack(window_losses)))))
    # a loaded program's temporaries are reserved, not in use: the peak
    # counts both
    stats = [d.memory_stats() or {} for d in devices]
    log(f"memory_stats {stats[0]}")
    peak_mem = max(s.get("peak_bytes_in_use", 0) + s.get("bytes_reserved", 0) for s in stats)
    log(f"window: {steps} steps in {elapsed:.3f} s, {tokens_per_s:.1f} tokens/s; set-up {setup_s:.2f} s; "
        f"peak {peak_mem} B")

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak_mem}
    result = {"correct": False, "attempted": steps, "failed": failed}
    if trace:
        (xplane,) = glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"), recursive=True)
        summary = tr.load_profile(xplane)
        shutil.rmtree(profile_dir, ignore_errors=True)
        log(f"traced window: {steps} steps, device op time {1e3 * sum(summary.op_s.values()) / steps!r} ms a step")
        ctx = types.SimpleNamespace(summary=summary, calls=calls, hlo=hlo, steps=steps, peaks=peaks,
                                    tokens=steps * B * S, chips=len(devices),
                                    flops_per_token=R.model_flops_per_token(m, S))
        metrics = {}
        for spec in cell["per_layer"]:
            value = load_reader(spec["name"])(ctx)
            if value is not None:
                metrics[spec["name"]] = {"value": value, "unit": spec["unit"]}
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        breakdown = {"device_ops": summary.top_ops(10),
                     "idle_gaps": [list(g) for g in summary.idle_gaps[:10]]}
    else:
        values = {"train_tokens_per_s": tokens_per_s, "setup_s": setup_s}
        metrics = {s["name"]: {"value": values[s["name"]], "unit": s["unit"]} for s in cell["end_to_end"]}
        breakdown = None

    # ---- the reference, on the state the program no longer holds
    del state, met, compiled, step_fn, window_losses, inflight, b, reader, readings
    gc.collect()
    jax.clear_caches()
    log(f"freed for the reference: memory_stats {devices[0].memory_stats()}")
    t_ref = time.perf_counter()
    batches = [stream.batch(i) for i in range(n_check)]
    ref = R.reference_readings(key, m, num, "f32", lr, batches)
    correct, numbers, detail = cmp.compare(prog, ref, cell["limits"])
    log(f"reference losses {ref['loss']} ({time.perf_counter() - t_ref:.1f} s); worst leaves {detail}; "
        f"memory_stats {devices[0].memory_stats()}")
    log("readings " + json.dumps({"prog": prog, "ref": ref}))

    result.update(correct=correct, metrics=metrics, device=device)
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["check"] = numbers
    return result


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        sys.exit(f"bench: the program's sources are not at {ROOT / 'src'}")
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    cell = load_cell(args.workload)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"bench: needs a TPU; JAX found {devices[0].platform!r}")
    if len(devices) < cell["chips"]:
        sys.exit(f"bench: the cell needs {cell['chips']} chips, JAX found {len(devices)}")
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if devices[0].device_kind not in table:
        sys.exit(f"bench: {devices[0].device_kind!r} is not in bench/peaks.json")
    # the cache's path is part of its key: a fixed directory in the checkout
    cache = str(ROOT / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), table[devices[0].device_kind])
    for name, n in result["check"].items():
        print(f"check {name} {n['value']!r} limit {n['limit']!r}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
