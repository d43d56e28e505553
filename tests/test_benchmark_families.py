"""The seam by which the benchmark finds what depends on an architecture
(benchmark/lib/spec.py `load_family`), held in tier-1: with no jax and no
process started, every configuration of BENCHMARK.json resolves to its
family's directory, the harness's own process loads the counts alone, and
each family has its three modules with the functions the harness calls.
(benchmark/tests/test_families.py holds the same cases among the
benchmark's own tests, which tier-1 does not run.)"""

import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH)

from lib import spec  # noqa: E402

FAMILY_OF = {"mistral-7b-train": "llama", "mistral-7b-serve": "llama",
             "minicpm-sala-serve": "minicpm_sala",
             "lfm2-24b-a2b-serve": "lfm2_moe"}
FUNCTIONS = {
    "program": ("serving", "training"),
    "reference": ("init_on_device", "served_logits", "follow_training"),
    "counts": ("cache_bytes", "decode_step_bytes", "train_flops_per_token",
               "flash_call_flops", "flash_call_bytes")}


def _config(name):
    path = os.path.join(BENCH, "configs", name + ".json")
    with open(path) as f:
        return path, json.load(f)


def test_every_configuration_of_the_benchmark_is_listed_here():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        assert {c["name"] for c in json.load(f)["configs"]} == set(FAMILY_OF)


@pytest.mark.parametrize("name", sorted(FAMILY_OF))
def test_a_configuration_resolves_to_its_family(name):
    path, cfg = _config(name)
    assert cfg.get("family", spec.DEFAULT_FAMILY) == FAMILY_OF[name]
    family = spec.load_family(path, cfg)
    assert family.name == FAMILY_OF[name]
    assert family.directory == os.path.join(BENCH, "families",
                                            FAMILY_OF[name])
    # the harness's own process reads the counts and nothing else
    assert family.counts.cache_bytes(cfg, 1, 64) > 0
    assert set(vars(family)) == {"name", "directory", "counts"}
    assert "jax" not in sys.modules or name    # (other tests may load jax)


def test_an_unknown_family_exits_and_names_where_it_looked(tmp_path):
    path = tmp_path / "configs" / "x.json"
    with pytest.raises(SystemExit) as e:
        spec.load_family(str(path), {"family": "no-such"})
    msg = str(e.value)
    assert "'no-such'" in msg
    assert str(tmp_path / "families" / "no-such") in msg
    assert os.path.join(BENCH, "families", "no-such") in msg


def test_a_family_beside_a_configuration_outside_the_benchmark_is_found(
        tmp_path):
    (tmp_path / "configs").mkdir()
    (tmp_path / "families" / "other").mkdir(parents=True)
    (tmp_path / "families" / "other" / "counts.py").write_text(
        "def cache_bytes(cfg, slots, budget, itemsize=2):\n    return 7\n")
    path = tmp_path / "configs" / "x.json"
    family = spec.load_family(str(path), {"family": "other"})
    assert family.directory == str(tmp_path / "families" / "other")
    assert family.counts.cache_bytes({}, 1, 1) == 7
    with pytest.raises(SystemExit) as e:        # a part it does not have
        family.reference
    assert "reference.py" in str(e.value)
    assert spec.load_family(str(path), {}).directory == os.path.join(
        BENCH, "families", "llama")


@pytest.mark.parametrize("family", sorted(set(FAMILY_OF.values())))
def test_a_family_has_its_three_modules_and_their_functions(family):
    src = {part: open(os.path.join(BENCH, "families", family,
                                   part + ".py")).read()
           for part in spec.Family.PARTS}
    for part, names in FUNCTIONS.items():
        for name in names:
            assert re.search(rf"^def {name}\(", src[part], re.M), name
    assert "tony_tpu" not in src["reference"] + src["counts"]
    assert "import" not in src["counts"].replace(
        "from __future__ import annotations", "")


def test_nothing_outside_the_families_names_an_architecture():
    """lib/, launch/, metrics/ and run.py find the program's model, the
    reference and the counts through the family alone."""
    named = re.compile(r"models\.(llama|sala|lfm2)|"
                       r"models import (llama|sala|lfm2)|Lfm2Config|"
                       r"LlamaConfig|SalaConfig|llama_init|sala_init|"
                       r"llama_loss|"
                       r"from lib import [^\n]*\b(reference|counts)\b|"
                       r"from families")
    hits = []
    for sub in ("lib", "launch", "metrics", "run.py"):
        top = os.path.join(BENCH, sub)
        files = [top] if sub.endswith(".py") else [
            os.path.join(top, f) for f in sorted(os.listdir(top))
            if f.endswith(".py")]
        hits += [f for f in files if named.search(open(f).read())]
    assert hits == []


def test_a_configuration_of_the_new_family_states_what_it_assumed():
    _, cfg = _config("minicpm-sala-serve")
    assert cfg["reduced"] == ["num_hidden_layers", "mixer_types"]
    assert set(cfg["reduced"]) <= set(cfg["changed"])
    sizes = {k for k, v in cfg["assumed"].items() if isinstance(v, dict)}
    assert sizes == {"sparse_config", "depth_for_scale"}
    assert all(v["origin"] for k, v in cfg["assumed"].items()
               if isinstance(v, dict))
    assert cfg["mixer_types"] == ["minicpm4"] + ["lightning-attn"] * 3 \
        + cfg["mixer_types"][4:] and len(cfg["mixer_types"]) == 16


def test_the_expert_configuration_is_the_published_one_cut_in_depth_alone():
    """lfm2-24b-a2b-serve: every number of the source's config under the
    source's key, the depth and the layer list (a prefix) alone reduced,
    each assumed size with its origin, and the bytes the file states are
    the family's counts."""
    path, cfg = _config("lfm2-24b-a2b-serve")
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types"]
    assert set(cfg["reduced"]) <= set(cfg["changed"])
    published = ["conv", "conv"] + ["full_attention", "conv", "conv",
                                    "conv"] * 2
    assert cfg["layer_types"] == published and cfg["num_hidden_layers"] == 10
    assert (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"], cfg["num_experts"],
            cfg["num_experts_per_tok"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["vocab_size"],
            cfg["conv_L_cache"], cfg["num_dense_layers"]) == (
        2048, 11776, 1536, 64, 4, 32, 8, 65536, 3, 2)
    assert all(v["origin"] for v in cfg["assumed"].values()
               if isinstance(v, dict))
    counts = spec.load_family(path, cfg).counts
    assert counts.total_params(cfg) == 5_267_090_176
    run = cfg["run"]
    assert counts.cache_bytes(cfg, run["slots"], run["token_budget"]) \
        == 64 * 8192 * 4096 + 64 * 8 * 3 * 2048 * 4
    # a step that hits 57 of 64 experts a layer reads 8.6 of its 9.6 GB
    # from the experts; the least a caller may be told is top-4's
    full = counts.decode_step_bytes(cfg, [50_000], experts_hit=57,
                                    riders=34)
    assert 9.5e9 < full < 9.8e9
    assert counts.decode_step_bytes(cfg, [50_000]) < 0.2 * full
