"""How a configuration's family is found (lib/spec.py), with no jax and
no process started: what a tier-1 guard of the seam would hold."""

import json
import os
import re

import pytest
from conftest import BENCH

from lib import spec


def _config(name):
    path = os.path.join(BENCH, "configs", name + ".json")
    with open(path) as f:
        return path, json.load(f)


@pytest.mark.parametrize("name", ["mistral-7b-train", "mistral-7b-serve"])
def test_a_configuration_without_a_family_is_of_the_one_that_was_there(name):
    path, cfg = _config(name)
    assert "family" not in cfg
    family = spec.load_family(path, cfg)
    assert family.name == "llama"
    assert family.directory == os.path.join(BENCH, "families", "llama")
    named = spec.load_family(path, dict(cfg, family="llama"))
    assert (named.name, named.directory) == (family.name, family.directory)
    # the harness's own process reads the counts and nothing else
    assert family.counts.cache_bytes(cfg, 1, 1) > 0
    assert set(vars(family)) == {"name", "directory", "counts"}


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
    # and one that states no family falls back to benchmark/families/llama
    assert spec.load_family(str(path), {}).directory == os.path.join(
        BENCH, "families", "llama")


def test_a_family_has_its_three_modules_and_their_functions():
    src = {part: open(os.path.join(BENCH, "families", "llama",
                                   part + ".py")).read()
           for part in spec.Family.PARTS}
    for part, names in {
            "program": ("serving", "training"),
            "reference": ("init_on_device", "served_logits",
                          "follow_training"),
            "counts": ("cache_bytes", "decode_step_bytes",
                       "train_flops_per_token", "flash_call_flops",
                       "flash_call_bytes")}.items():
        for name in names:
            assert re.search(rf"^def {name}\(", src[part], re.M), name
    assert "tony_tpu" not in src["reference"] + src["counts"]
    assert "import" not in src["counts"].replace(
        "from __future__ import annotations", "")


def test_nothing_outside_the_families_names_an_architecture():
    """lib/, launch/, metrics/ and run.py find the program's model, the
    reference and the counts through the family alone."""
    named = re.compile(r"models\.llama|models import llama|LlamaConfig|"
                       r"llama_init|llama_loss|"
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
