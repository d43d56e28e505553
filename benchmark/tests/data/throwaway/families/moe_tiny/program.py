"""The program's side of a throwaway family (benchmark/tests/test_extend.py
adds it to a copy of the benchmark as new files): the program's own
mixture-of-experts model, `models/moe.py`, whose block is not Llama's.
Capacity is set so that no token is dropped, and the load-balancing loss
is off: the reference beside this file models neither."""

from __future__ import annotations

from functools import partial

from lib import inproc


def program_config(cfg: dict):
    import jax.numpy as jnp
    from tony_tpu.models.moe import MoEConfig
    experts, top_k = cfg["num_local_experts"], cfg["num_experts_per_tok"]
    kw = dict(vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
              n_layers=cfg["num_hidden_layers"],
              n_heads=cfg["num_attention_heads"],
              n_kv_heads=cfg["num_key_value_heads"],
              ffn_dim=cfg["intermediate_size"],
              max_seq=cfg["run"]["max_seq"],
              rope_theta=float(cfg["rope_theta"]),
              norm_eps=float(cfg["rms_norm_eps"]),
              dtype={"float32": jnp.float32}[cfg["torch_dtype"]],
              n_experts=experts, top_k=top_k,
              capacity_factor=experts / top_k, aux_loss_weight=0.0)
    kw.update(cfg["run"].get("program", {}))
    return MoEConfig(**kw)


def serving(cfg: dict, seed: int) -> str:
    from tony_tpu.models import moe
    moe.PRESETS["benchmark-moe"] = program_config(cfg)
    program_init = moe.moe_init
    moe.moe_init = lambda c, _key: inproc.seeded_init(program_init, c, seed)
    return "benchmark-moe"


def training(cfg: dict, seed: int) -> dict:
    from tony_tpu.models.moe import moe_init, moe_loss, moe_param_axes
    config = program_config(cfg)
    return {"config": config, "init": moe_init,
            "loss_fn": partial(moe_loss, config=config),
            "init_fn": lambda _key: inproc.seeded_init(moe_init, config,
                                                       seed),
            "param_axes": moe_param_axes(config)}
