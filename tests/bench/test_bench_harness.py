"""bench/run.py takes a configuration of any block pattern: the program's
``LMConfig`` from every field the configuration file's ``config`` gives, the
plain reference module the file names, and the model FLOPs that reference
counts."""
import dataclasses
import json
import pathlib
import sys
import types

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

from bench_cells import PEAKS, ROOT, SEED, config_file, control, run, tiny  # noqa: E402

from bench import refmodel  # noqa: E402
from bench import trace_reduce as tr  # noqa: E402

DATA = pathlib.Path(__file__).parent / "data"
# DeepSeek-V2-Lite's block pattern at the repository's smoke sizes
# (repro.configs.deepseek_v2_lite_16b.SMOKE), as a configuration file writes it
DEEPSEEK = {
    "pattern": [["mla_dense", 1], ["mla_moe", 2]],
    "d_model": 64, "n_layers": 3, "vocab": 256, "n_heads": 4, "n_kv_heads": 4, "head_dim": 16, "d_ff": 32,
    "act": "silu", "rope_theta": 10000.0, "norm_eps": 1e-06, "tie_embeddings": False, "dense_ff_prefix": 96,
    "mla": {"kv_lora_rank": 32, "qk_nope_dim": 16, "qk_rope_dim": 8, "v_head_dim": 16},
    "moe": {"n_experts": 8, "top_k": 2, "d_ff_expert": 32, "n_shared": 1, "d_ff_shared": 32,
            "capacity_factor": 8.0},
    "dtype": "float32",
}


def fixed_fields_config(cell):
    """The ``LMConfig`` as the harness built it from a fixed list of dense
    fields, in one group of ``block``."""
    import jax.numpy as jnp
    from repro.models.common import LMConfig

    conf = cell["config"]
    fields = ("d_model", "n_layers", "vocab", "n_heads", "n_kv_heads", "head_dim", "d_ff", "act",
              "rope_theta", "norm_eps", "tie_embeddings", "qk_norm")
    return LMConfig(arch_id=cell["config_name"], pattern=((conf["block"], conf["n_layers"]),),
                    dtype=jnp.dtype(conf["dtype"]), **{k: conf[k] for k in fields})


@pytest.mark.parametrize("name", ["chameleon_34b-1L", "phi4_mini_3p8b-2L"])
def test_program_config_of_the_dense_files_is_as_before(name):
    cell = {"config_name": name, "config": config_file(name)["config"]}
    assert run.program_config(cell) == fixed_fields_config(cell)


def test_program_config_of_a_deepseek_pattern():
    from repro.configs.deepseek_v2_lite_16b import SMOKE
    from repro.models.common import MLACfg, MoECfg

    cfg = run.program_config({"config_name": "deepseek-tiny", "config": json.loads(json.dumps(DEEPSEEK))})
    assert cfg.pattern == (("mla_dense", 1), ("mla_moe", 2))
    assert isinstance(cfg.mla, MLACfg) and isinstance(cfg.moe, MoECfg)
    assert dataclasses.replace(cfg, arch_id=SMOKE.arch_id, dtype=SMOKE.dtype) == SMOKE


@pytest.mark.parametrize("bad,match", [
    ({"moe": DEEPSEEK["moe"] | {"n_routed": 8}}, "moe: MoECfg has no field n_routed"),
    ({"mla": DEEPSEEK["mla"] | {"q_lora_rank": 0}}, "mla: MLACfg has no field q_lora_rank"),
])
def test_program_config_refuses_a_field_the_program_lacks(bad, match):
    with pytest.raises(ValueError, match=match):
        run.program_config({"config_name": "deepseek-tiny", "config": DEEPSEEK | bad})


def test_program_config_takes_the_pattern_over_block():
    conf = config_file("phi4_mini_3p8b-2L")["config"]
    cell = {"config_name": "phi4", "config": conf | {"pattern": [["dense", 2]]}}
    assert run.program_config(cell) == run.program_config({"config_name": "phi4", "config": conf})


FIXTURE_REFERENCE = '''"""A reference for the tests: bench/refmodel.py, recording its calls."""
from bench.refmodel import *  # noqa: F401,F403
from bench import refmodel as _dense

CALLS = []


def reference_readings(key, m, num, mode, lr, batches):
    CALLS.append(("reference_readings", mode))
    return _dense.reference_readings(key, m, num, mode, lr, batches)


def model_flops_per_token(m, seq):
    CALLS.append(("model_flops_per_token", seq))
    return _dense.model_flops_per_token(m, seq)
'''


@pytest.fixture
def fixture_reference(tmp_path, monkeypatch):
    """``bench.ref_fixture``: a module under the benchmark's package."""
    import bench

    (tmp_path / "ref_fixture.py").write_text(FIXTURE_REFERENCE)
    monkeypatch.setattr(bench, "__path__", [*bench.__path__, str(tmp_path)])
    yield "ref_fixture"
    sys.modules.pop("bench.ref_fixture", None)


def test_load_reference_imports_the_module_a_file_names(fixture_reference):
    R = run.load_reference({"reference": fixture_reference, "config": DEEPSEEK})
    assert R.__name__ == "bench.ref_fixture" and R.CALLS == []
    assert R.Numerics is refmodel.Numerics
    assert run.load_reference({"config": DEEPSEEK}) is refmodel
    assert run.load_cell("phi4-train")["reference"] == "refmodel"
    for bad in ("../refmodel", "refmodel.py", "bench.refmodel", ""):
        with pytest.raises(ValueError, match="not the name of a module"):
            run.load_reference({"reference": bad})


def test_a_run_and_the_control_drive_the_reference_the_file_names(fixture_reference):
    cell = dict(tiny("phi4-train"), reference=fixture_reference)
    R = run.load_reference(cell)
    res = run.run_cell(cell, SEED, 0.3, False, PEAKS)
    assert res["correct"], res["check"]
    assert R.CALLS == [("reference_readings", "f32")]
    R.CALLS.clear()
    control.stand_in_readings(cell, SEED, modes=("control",))
    assert R.CALLS == [("reference_readings", "f32"), ("reference_readings", "fp8")]


@pytest.mark.parametrize("conf", [
    {"pattern": [["mla_dense", 1], ["mla_moe", 2]]},
    {"block": "mla_moe"},
    {"pattern": [["dense", 1], ["dense", 1]]},
    {"pattern": [["dense", 3]]},
    {"mla": DEEPSEEK["mla"]},
    {"window": 512},
])
def test_dense_reference_refuses_what_it_does_not_model(conf):
    dense = config_file("phi4_mini_3p8b-2L")["config"]
    with pytest.raises(ValueError, match="dense reference"):
        refmodel.Model.from_config(dense | conf)


def test_dense_reference_takes_a_dense_pattern():
    dense = config_file("phi4_mini_3p8b-2L")["config"]
    m = refmodel.Model.from_config(dense)
    assert refmodel.Model.from_config(dense | {"pattern": [["dense", 2]]}) == m
    assert refmodel.Model.from_config({k: v for k, v in dense.items() if k != "block"}) == m


def test_mfu_reads_as_before_on_the_recorded_window():
    """``mfu.train`` on the recorded phi4-train window (11 steps of 4 x 512
    tokens), with the count the reference now makes: the reader's own count
    gave 4,914,450,432 FLOPs a token at 512 positions (4,905,013,248 at the
    adc9 cell's 256) and the share 30.4787697121295%."""
    f = config_file("phi4_mini_3p8b-2L")
    R = run.load_reference(f)
    m = R.Model.from_config(f["config"])
    assert R.model_flops_per_token(m, 512) == 4914450432.0
    assert R.model_flops_per_token(m, 256) == 4905013248.0
    peaks = json.loads((ROOT / "bench" / "peaks.json").read_text())["devices"]["TPU v5 lite"]
    ctx = types.SimpleNamespace(summary=tr.load_profile(str(DATA / "phi4-train.scoped.xplane.pb")), peaks=peaks,
                                tokens=11 * 4 * 512, chips=1, flops_per_token=R.model_flops_per_token(m, 512))
    assert run.load_reader("mfu.train")(ctx) == pytest.approx(30.4787697121295, rel=1e-12)
    assert run.load_reader("mfu.train")(types.SimpleNamespace(**ctx.__dict__ | {"tokens": 0})) is None
