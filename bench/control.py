#!/usr/bin/env python3
"""The control and the planted faults of a cell, read on the chip at the
cell's own size:

    python3 bench/control.py --workload <cell> --seeds <n> [<n> ...]

For each seed the reference (float32; the module the configuration names,
``bench/run.py``'s ``load_reference``) runs the cell's first steps, and then
three stand-ins take the program's place and are compared with it exactly as
``bench/run.py`` compares the program:

* ``control``: the reference with every matrix product outside the crossbar
  reads in float8 e4m3 (per-tensor scaled), the precision below the
  program's bfloat16;
* ``adc_below``, in a cell with an analog read: the reference with ADCs one
  bit coarser than the cell states, the precision below its 9-bit reads;
* ``half_batch``: the reference fed the first half of each batch's rows, the
  mean taken over them;
* ``unchanged``: a step that returns its state unchanged; its change gaps
  read 1 by construction and need no run.

The numbers compared, per seed and stand-in, are the last stdout line as
JSON. ``bench/limits/<cell>.json`` records them beside the program's own.
Not part of a benchmark run.
"""
import argparse
import dataclasses
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]


def stand_in_readings(cell: dict, seed: int, modes=("control", "adc_below", "half_batch")) -> dict:
    """-> {"reference": readings, <stand-in>: readings} for one seed."""
    from bench import run
    from bench import tokens as tok
    from bench.refmodel import Numerics

    R = run.load_reference(cell)
    traffic = cell["traffic"]
    m = R.Model.from_config(cell["config"])
    num = Numerics.from_traffic(traffic)
    B, S, lr, n = traffic["batch"], traffic["seq"], traffic["lr"], run.CHECK_STEPS
    key = R.seed_key(seed)
    stream = tok.TokenStream(m.vocab, S, B, seed)
    batches = [stream.batch(i) for i in range(n)]
    out = {"reference": R.reference_readings(key, m, num, "f32", lr, batches)}
    if "control" in modes:
        out["control"] = R.reference_readings(key, m, num, "fp8", lr, batches)
    if "adc_below" in modes and num.analog:
        coarser = dataclasses.replace(num, adc_bits_fwd=num.adc_bits_fwd - 1, adc_bits_bwd=num.adc_bits_bwd - 1)
        out["adc_below"] = R.reference_readings(key, m, coarser, "f32", lr, batches)
    if "half_batch" in modes:
        half = [(x[: B // 2], y[: B // 2]) for x, y in batches]
        out["half_batch"] = R.reference_readings(key, m, num, "f32", lr, half)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    import jax

    from bench import compare as cmp
    from bench import run

    if jax.devices()[0].platform != "tpu":
        sys.exit("control: needs a TPU")
    cell = run.load_cell(args.workload)
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    rows = []
    for seed in args.seeds:
        t0 = time.perf_counter()
        got = stand_in_readings(cell, seed)
        for name in (k for k in got if k != "reference"):
            _, numbers, detail = cmp.compare(got[name], got["reference"], cell["limits"])
            row = {"seed": seed, "stand_in": name, **{k: v["value"] for k, v in numbers.items()}, **detail}
            rows.append(row)
            print(json.dumps(row), flush=True)
        print(f"seed {seed}: {time.perf_counter() - t0:.1f} s; losses "
              f"{ {k: v['loss'] for k, v in got.items()} }", file=sys.stderr, flush=True)
    print(json.dumps(rows))


if __name__ == "__main__":
    main()
