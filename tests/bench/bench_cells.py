"""The benchmark's cells at a size a CPU holds, for the tests beside this file."""
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


# every cell the benchmark's files describe, whether BENCHMARK.json runs it
# yet or not: (configuration, traffic mix)
CELLS = {
    "phi4-train": ("phi4_mini_3p8b-2L", "lossless-4x512"),
    "phi4-train-adc9": ("phi4_mini_3p8b-2L", "adc9-2x256"),
    "chameleon-train-2k": ("chameleon_34b-1L", "lossless-2x2048"),
}


def tiny(name):
    """The cell at CPU size, every width a multiple of the 128-row crossbar,
    with the limits of that size."""
    conf, traffic = CELLS[name]
    e2e = json.loads((ROOT / "BENCHMARK.json").read_text())["end_to_end"]
    cell = {"name": name, "chips": 1, "config_name": conf, "end_to_end": e2e, "per_layer": [],
            "config": json.loads((ROOT / "bench" / "configs" / f"{conf}.json").read_text())["config"],
            "traffic": json.loads((ROOT / "bench" / "traffic" / f"{traffic}.json").read_text())}
    cell["config"] = dict(cell["config"], d_model=128, n_layers=2, vocab=512, n_heads=2,
                          n_kv_heads=1, head_dim=64, d_ff=256)
    cell["traffic"] = dict(cell["traffic"], batch=2, seq=32)
    cell["limits"] = TINY_LIMITS["adc9" if cell["traffic"]["fidelity"] else "lossless"]
    return cell
