"""BENCHMARK.json, the configuration files and the data each cell names.

Every configuration file under ``bench/configs/`` is checked: its
``published`` sizes against its ``config`` and ``reduced`` (nested objects
by dotted path, ``moe.n_experts``), and against the repository's config that
its ``program_config`` names."""
import dataclasses
import json
import pathlib
import re
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

from bench_cells import config_file, run  # noqa: E402

BM = json.loads((ROOT / "BENCHMARK.json").read_text())
CONFIGS = sorted(p.stem for p in (ROOT / "bench" / "configs").glob("*.json"))
# sizes that no cut may change, and that every model has
WIDTHS = ("d_model", "n_heads", "n_kv_heads", "head_dim", "d_ff")
# sizes that every configuration file states and the repository's config has
SIZES = ("d_model", "n_layers", "vocab")


def flat(obj: dict, prefix: str = "") -> dict:
    """A configuration object with its nested objects' keys as dotted paths."""
    out = {}
    for k, v in obj.items():
        if isinstance(v, dict):
            out |= flat(v, f"{prefix}{k}.")
        else:
            out[prefix + k] = v
    return out


def is_width(key: str) -> bool:
    """A hidden, intermediate, latent, state, window or projection size, a
    head size or count, an expansion factor or the experts per token: never
    cut."""
    last = key.rsplit(".", 1)[-1]
    return last in WIDTHS or last in ("top_k", "expand", "window", "dense_ff_prefix") \
        or last.startswith("d_") or last.endswith(("_dim", "_rank", "_factor", "_heads", "_width"))


def as_json(value):
    """A program config's value as its configuration file writes it."""
    if dataclasses.is_dataclass(value):
        return {f.name: as_json(getattr(value, f.name)) for f in dataclasses.fields(value)}
    if isinstance(value, tuple):
        return [as_json(v) for v in value]
    return value


def test_every_configuration_is_checked():
    assert {"phi4_mini_3p8b-2L", "chameleon_34b-1L"} <= set(CONFIGS)
    assert {c["file"] for c in BM["configs"]} <= {f"bench/configs/{n}.json" for n in CONFIGS}


@pytest.mark.parametrize("name", CONFIGS)
def test_config_file_keeps_published_widths_and_lists_every_cut(name):
    f = config_file(name)
    held, pub = flat(f["config"]), flat(f["published"])
    changed = sorted(k for k in pub if held[k] != pub[k])
    assert changed == sorted(f["reduced"])
    assert "d_model" in pub
    for k in {*pub, *held}:
        if is_width(k):
            assert held[k] == pub[k], k
    for k, cut in f["reduced"].items():
        assert cut["published"] == pub[k] and cut["held"] == held[k]
    assert f["source"] and f["deployment"]
    for conf in BM["configs"]:
        if conf["name"] == name:
            assert conf["file"] == f"bench/configs/{name}.json"
            assert sorted(conf["reduced"]) == changed
            assert conf["source"] == f["source"]


@pytest.mark.parametrize("name", CONFIGS)
def test_config_file_matches_the_repositorys_published_config(name):
    sys.path.insert(0, str(ROOT / "src"))
    from repro import configs
    from repro.models.common import LMConfig

    f = config_file(name)
    repo = configs.get(f["program_config"])
    fields = {fl.name for fl in dataclasses.fields(LMConfig)}
    pub = flat(f["published"])
    assert set(SIZES) <= set(pub)
    for k in pub:
        assert k.split(".")[0] in fields, f"{k} names no field of the program's config"
        value = repo
        for part in k.split("."):
            value = getattr(value, part)
        assert as_json(value) == pub[k], k


@pytest.mark.parametrize("name", CONFIGS)
def test_config_file_names_a_reference_that_models_it(name):
    f = config_file(name)
    R = run.load_reference(f)
    for attr in ("seed_key", "gen_params", "nest", "Reader", "reference_readings", "describe",
                 "model_flops_per_token"):
        assert callable(getattr(R, attr)), attr
    m = R.Model.from_config(f["config"])
    assert m.vocab == f["config"]["vocab"]
    assert R.model_flops_per_token(m, 512) > R.model_flops_per_token(m, 256) > 0


def test_phi4_and_chameleon_published_sizes():
    phi_file = config_file("phi4_mini_3p8b-2L")
    R = run.load_reference(phi_file)
    phi = R.Model.from_config(phi_file["published"] | {"n_layers": 32})
    assert (phi.d_model, phi.n_heads, phi.n_kv_heads, phi.head_dim, phi.d_ff, phi.vocab) == \
        (3072, 24, 8, 128, 8192, 200064)
    cham_file = config_file("chameleon_34b-1L")
    cham = cham_file["config"]
    assert (cham["d_model"], cham["n_heads"], cham["d_ff"], cham["vocab"]) == (8192, 64, 22016, 65536 // 8)
    # one chameleon layer: q/k/v, attention output and SwiGLU projections
    R = run.load_reference(cham_file)
    layer = R.Model.from_config(cham)
    per_layer = 8192 * 80 * 128 + 64 * 128 * 8192 + 3 * 8192 * 22016
    assert R.n_params(layer) == per_layer + 2 * 8192 * 8192 + 3 * 8192 + 2 * 128
    assert round(per_layer / 1e6) == 692


def test_every_cell_names_files_that_exist():
    names = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
    cells = {w["name"] for w in BM["workloads"]}
    for w in BM["workloads"]:
        assert names.match(w["name"]) and names.match(w["traffic"]) and w["chips"] == 1
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").exists()
        limits = json.loads((ROOT / "bench" / "limits" / f"{w['name']}.json").read_text())["limits"]
        assert set(limits) == {"loss_gap", "grad1_gap", "change_gap", "frac_bits_off"}
    for m in BM["per_layer"]:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").exists()
        assert set(m["workloads"]) <= cells and m["moves"] == "train_tokens_per_s"
    assert {m["name"] for m in BM["end_to_end"]} == {"train_tokens_per_s", "setup_s"}
