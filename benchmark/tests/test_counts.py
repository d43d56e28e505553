import json
import os

import pytest
from conftest import BENCH

from families.llama import counts
from lib import peaks


def _cfg(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


# one Mistral-7B block, by hand: q and o 4096x4096, k and v 4096x1024,
# gate, up and down 4096x14336
LAYER = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336
HEAD = 4096 * 32000


@pytest.mark.parametrize("name,layers", [("mistral-7b-train", 5),
                                         ("mistral-7b-serve", 16)])
def test_parameter_counts_match_a_hand_count(name, layers):
    cfg = _cfg(name)
    assert cfg["num_hidden_layers"] == layers
    assert counts.head_dim(cfg) == 128
    assert counts.layer_matmul_params(cfg) == LAYER == 218_103_808
    assert counts.matmul_params(cfg) == layers * LAYER + HEAD
    assert counts.total_params(cfg) == (
        layers * LAYER + 2 * HEAD + layers * 2 * 4096 + 4096)


def test_train_flops_per_token_by_hand():
    cfg = _cfg("mistral-7b-train")
    # causal attention, forward, a token and a layer: QK^T and PV are each
    # 2 * S * hd a head, over 32 heads, halved by the mask
    attn = 4 * 32 * 4096 * 128 / 2
    want = 3 * (2 * (5 * LAYER + HEAD) + 5 * attn)
    assert counts.train_flops_per_token(cfg, 4096) == pytest.approx(want)
    assert want == pytest.approx(7.83e9, rel=2e-3)
    # the embedding lookup is not in it: 6 * (all parameters) is larger
    assert want - 3 * 5 * attn < 6 * counts.total_params(cfg)


def test_flash_kernel_counts_by_hand():
    cfg = _cfg("mistral-7b-train")
    one = 2 * 2 * 32 * 4096 * 4096 * 128 / 2     # one S x S x hd matmul
    assert counts.flash_call_flops(cfg, 4096, 2, "fwd") == 2 * one
    assert counts.flash_call_flops(cfg, 4096, 2, "bwd_dq") == 3 * one
    assert counts.flash_call_flops(cfg, 4096, 2, "bwd_dkv") == 4 * one
    q = 2 * 32 * 4096 * 128 * 2
    kv = 2 * 8 * 4096 * 128 * 2
    lse = 2 * 32 * 4096 * 4
    assert counts.flash_call_bytes(cfg, 4096, 2, "fwd") == 2 * q + 2 * kv + lse
    assert counts.flash_call_bytes(cfg, 4096, 2, "bwd_dq") == (
        4 * q + 2 * kv + lse)
    assert counts.flash_call_bytes(cfg, 4096, 2, "bwd_dkv") == (
        3 * q + 4 * kv + lse)
    pk = peaks.peaks_of("TPU v5 lite")
    # at 4096 tokens the kernels are compute-bound on a v5e
    assert (2 * one / pk["bf16_flops_per_s"]
            > (2 * q + 2 * kv + lse) / pk["hbm_bytes_per_s"])


def test_decode_bytes_and_cache_by_hand():
    cfg = _cfg("mistral-7b-serve")
    weights = (16 * LAYER + HEAD) * 2
    assert counts.decode_step_bytes(cfg, []) == weights
    row = 2 * 16 * 8 * 128 * 2                     # K and V of one token
    assert counts.decode_step_bytes(cfg, [100, 300]) == weights + 400 * row
    assert counts.cache_bytes(cfg, 32, 2048) == 32 * 2048 * row
    # what PR 24 read on the chip: 7.5 GB + 4.3 GB = 11.85 GB
    total = (16 * LAYER + 2 * HEAD) * 2 + counts.cache_bytes(cfg, 32, 2048)
    assert total == pytest.approx(11.85e9, rel=5e-3)


def test_an_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        peaks.peaks_of("TPU v9")
    assert peaks.peaks_of("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
