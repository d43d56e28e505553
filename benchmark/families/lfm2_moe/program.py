"""The program's side of the `lfm2_moe` family: how a configuration file
of LFM2-MoE (gated short-convolution and GQA attention layers, dense MLPs
in the leading layers and sparse experts after them) becomes the program's
`Lfm2Config`, and how the serving replica is given it with weights made on
the device from the seed. The only module of the family that imports the
program; the replica launcher (benchmark/launch/replica.py) calls it from
the process that holds the chip. The family is served, not trained.
"""

from __future__ import annotations

from lib import inproc


def program_config(cfg: dict):
    """The program's Lfm2Config from a configuration file's keys (the
    source's names)."""
    import jax.numpy as jnp
    from tony_tpu.models.lfm2 import Lfm2Config
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        cfg["torch_dtype"]]
    if cfg.get("conv_bias") or not cfg.get("use_expert_bias", True):
        raise SystemExit("benchmark: the program's conv layers have no "
                         "bias and its router always adds its expert bias")
    heads = cfg["num_attention_heads"]
    kw = dict(vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
              n_layers=cfg["num_hidden_layers"],
              layer_types=tuple(cfg["layer_types"]),
              n_dense_layers=cfg["num_dense_layers"], n_heads=heads,
              n_kv_heads=cfg["num_key_value_heads"],
              head_dim=cfg.get("head_dim") or cfg["hidden_size"] // heads,
              ffn_dim=cfg["intermediate_size"],
              expert_dim=cfg["moe_intermediate_size"],
              n_experts=cfg["num_experts"],
              top_k=cfg["num_experts_per_tok"],
              norm_topk=bool(cfg["norm_topk_prob"]),
              routed_scale=float(cfg["routed_scaling_factor"]),
              conv_kernel=cfg["conv_L_cache"],
              max_seq=cfg["run"]["max_seq"],
              norm_eps=float(cfg["norm_eps"]),
              rope_theta=float(cfg["rope_parameters"]["rope_theta"]),
              dtype=dtype)
    kw.update(cfg["run"].get("program", {}))
    return Lfm2Config(**kw)


def serving(cfg: dict, seed: int) -> str:
    """Install the configuration into the program as a preset whose
    weights come from the seed; returns the preset's name, the `--config`
    of `tony_tpu.serve.__main__.main`."""
    from tony_tpu.models import lfm2
    lfm2.PRESETS["benchmark"] = program_config(cfg)
    program_init = lfm2.lfm2_init
    lfm2.lfm2_init = lambda c, _key: inproc.seeded_init(program_init, c,
                                                        seed)
    return "benchmark"


def training(cfg: dict, seed: int) -> dict:
    raise SystemExit("benchmark: the lfm2_moe family is served, not "
                     "trained: the program's expert layer without dropped "
                     "tokens has no training path (PERF.md section 7)")
