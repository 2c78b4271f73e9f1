"""BENCHMARK.json, the configuration files and the data each cell names."""
import json
import pathlib
import re
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import refmodel as R  # noqa: E402

BM = json.loads((ROOT / "BENCHMARK.json").read_text())
WIDTHS = ("d_model", "n_heads", "n_kv_heads", "head_dim", "d_ff")
# the benchmark's configuration files and the repository's configs they follow
PROGRAM_CONFIG = {"phi4_mini_3p8b-2L": "phi4_mini_3p8b", "chameleon_34b-1L": "chameleon_34b"}


def config_file(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", sorted(PROGRAM_CONFIG))
def test_config_file_keeps_published_widths_and_lists_every_cut(name):
    f = config_file(name)
    run, pub = f["config"], f["published"]
    changed = sorted(k for k in pub if run[k] != pub[k])
    assert changed == sorted(f["reduced"])
    for k in WIDTHS:
        assert run[k] == pub[k]
    for k, cut in f["reduced"].items():
        assert cut["published"] == pub[k] and cut["held"] == run[k]
    assert f["source"] and f["deployment"]
    for conf in BM["configs"]:
        if conf["name"] == name:
            assert conf["file"] == f"bench/configs/{name}.json"
            assert sorted(conf["reduced"]) == changed and conf["source"] == f["source"]


@pytest.mark.parametrize("name", sorted(PROGRAM_CONFIG))
def test_config_file_matches_the_repositorys_published_config(name):
    sys.path.insert(0, str(ROOT / "src"))
    from repro import configs

    pub = config_file(name)["published"]
    repo = configs.get(PROGRAM_CONFIG[name])
    for k in (*WIDTHS, "n_layers", "vocab", "tie_embeddings", "rope_theta", "norm_eps"):
        assert getattr(repo, k) == pub[k], k


def test_phi4_and_chameleon_published_sizes():
    phi = R.Model.from_config(config_file("phi4_mini_3p8b-2L")["published"] | {"n_layers": 32})
    assert (phi.d_model, phi.n_heads, phi.n_kv_heads, phi.head_dim, phi.d_ff, phi.vocab) == \
        (3072, 24, 8, 128, 8192, 200064)
    cham = config_file("chameleon_34b-1L")["config"]
    assert (cham["d_model"], cham["n_heads"], cham["d_ff"], cham["vocab"]) == (8192, 64, 22016, 65536 // 8)
    # one chameleon layer: q/k/v, attention output and SwiGLU projections
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
