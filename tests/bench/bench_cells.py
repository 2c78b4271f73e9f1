"""The benchmark's cells at a size a CPU holds, for the tests beside this file.

The cells are ``BENCHMARK.json``'s ``workloads`` and the file-only cells of
``FILE_ONLY``. A configuration file's optional ``"cpu"`` object gives its
size here: ``"config"``, keys that replace the ``config`` object's of the
same name (a nested object whole; ``DENSE_CPU`` where it gives none). The
limits of that size are ``TINY_LIMITS``, by kind of traffic."""
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import compare as cmp  # noqa: E402,F401
from bench import control, run  # noqa: E402,F401

SEED = 2**33 + 12345
PEAKS = {"bf16_flops_per_s": 1e12, "int8_ops_per_s": 2e12, "hbm_bytes_per_s": 1e11}
# Limits at the tiny size, from CPU readings at SEED. Lossless cells: sound
# runs read at most 0.0007 / 0.0015 / 0.0009 (loss, first update, change),
# the float8 control at least 0.0042 / 0.0097 / 0.0093. The adc9 cell: a
# sound run reads 0.032 / 0.024 / 0.031 (one 128-row tile per read, so the
# 9-bit ADC's codes swing with the last bits of bf16 inputs), 8-bit ADCs
# 0.045 / 0.154 / 0.150; its loss separates nothing and is not compared.
TINY_LIMITS = {
    "lossless": {"loss_gap": 0.002, "grad1_gap": 0.004, "change_gap": 0.004, "frac_bits_off": 0},
    "adc9": {"loss_gap": None, "grad1_gap": 0.08, "change_gap": 0.08, "frac_bits_off": 0},
}
# a dense decoder at CPU size, every width a multiple of the 128-row crossbar
DENSE_CPU = {"d_model": 128, "n_layers": 2, "vocab": 512, "n_heads": 2, "n_kv_heads": 1, "head_dim": 64,
             "d_ff": 256}
BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())
# cells whose files are in bench/ but which BENCHMARK.json does not run
# (PERF.md's Open questions say why): (configuration, traffic mix)
FILE_ONLY = {"chameleon-train-2k": ("chameleon_34b-1L", "lossless-2x2048")}
# every cell the benchmark's files describe: (configuration, traffic mix)
CELLS = {w["name"]: (w["config"], w["traffic"]) for w in BENCHMARK["workloads"]} | FILE_ONLY


def config_path(conf: str) -> pathlib.Path:
    """The file of configuration ``conf``: BENCHMARK.json's, else
    ``bench/configs/<conf>.json``."""
    files = {c["name"]: c["file"] for c in BENCHMARK["configs"]}
    return ROOT / files.get(conf, f"bench/configs/{conf}.json")


def config_file(conf: str) -> dict:
    return json.loads(config_path(conf).read_text())


def tiny(name):
    """The cell at CPU size, with the limits of that size."""
    conf, traffic = CELLS[name]
    f = config_file(conf)
    cpu = f.get("cpu", {})
    cell = {"name": name, "chips": 1, "config_name": conf, "end_to_end": BENCHMARK["end_to_end"],
            "per_layer": [], "reference": f.get("reference", run.DEFAULT_REFERENCE),
            "config": f["config"] | cpu.get("config", DENSE_CPU),
            "traffic": json.loads((ROOT / "bench" / "traffic" / f"{traffic}.json").read_text())}
    cell["traffic"] = dict(cell["traffic"], batch=2, seq=32)
    kind = "adc9" if cell["traffic"]["fidelity"] else "lossless"
    cell["limits"] = TINY_LIMITS[kind]
    return cell

