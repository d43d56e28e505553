"""The program's side of the `llama` family: how a configuration file of
the Llama block (Mistral's is the same equations) becomes the program's
`LlamaConfig`, and how the serving replica and the trainer are given it
with weights made on the device from the seed. The only module of the
family that imports the program; the launchers (benchmark/launch/) call it
from the process that holds the chip.
"""

from __future__ import annotations

from functools import partial

from lib import inproc


def program_config(cfg: dict):
    """The program's LlamaConfig from a configuration file's keys (the
    source's names). Mistral's block is Llama's equations; its sliding
    window is not modelled and never binds at the lengths the cells use."""
    import jax.numpy as jnp
    from tony_tpu.models.llama import LlamaConfig
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        cfg["torch_dtype"]]
    kw = dict(vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
              n_layers=cfg["num_hidden_layers"],
              n_heads=cfg["num_attention_heads"],
              n_kv_heads=cfg["num_key_value_heads"],
              ffn_dim=cfg["intermediate_size"],
              max_seq=cfg["run"]["max_seq"],
              rope_theta=float(cfg["rope_theta"]),
              norm_eps=float(cfg["rms_norm_eps"]), dtype=dtype)
    kw.update(cfg["run"].get("program", {}))
    return LlamaConfig(**kw)


def serving(cfg: dict, seed: int) -> str:
    """Install the configuration into the program as a preset whose
    weights come from the seed; returns the preset's name, the `--config`
    of `tony_tpu.serve.__main__.main`."""
    from tony_tpu.models import llama
    llama.PRESETS["benchmark"] = program_config(cfg)
    program_init = llama.llama_init
    llama.llama_init = lambda c, _key: inproc.seeded_init(
        program_init, c, seed)
    return "benchmark"


def training(cfg: dict, seed: int) -> dict:
    """What the program's `Trainer` needs of the model (`loss_fn`,
    `init_fn`, `param_axes`), the program's `config`, and `init`: the
    program's own `init(config, key)`, from which the train worker draws
    the seed's leaves again to measure the parameters' change."""
    from tony_tpu.models.llama import (
        llama_init, llama_loss, llama_param_axes,
    )
    config = program_config(cfg)
    return {"config": config, "init": llama_init,
            "loss_fn": partial(llama_loss, config=config),
            "init_fn": lambda _key: inproc.seeded_init(llama_init, config,
                                                       seed),
            "param_axes": llama_param_axes(config)}
