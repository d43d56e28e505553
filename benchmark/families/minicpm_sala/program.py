"""The program's side of the `minicpm_sala` family: how a configuration
file of MiniCPM-SALA (layers of two kinds: `minicpm4` block-sparse
attention and `lightning-attn` linear attention) becomes the program's
`SalaConfig`, and how the serving replica is given it with weights made on
the device from the seed. The only module of the family that imports the
program; the replica launcher (benchmark/launch/replica.py) calls it from
the process that holds the chip. Sizes the source's config.json does not
give come from the configuration file's `assumed`, where each has its
origin. The family is served, not trained.
"""

from __future__ import annotations

from lib import inproc


def program_config(cfg: dict):
    """The program's SalaConfig from a configuration file's keys (the
    source's names)."""
    import jax.numpy as jnp
    from tony_tpu.models.sala import SalaConfig
    from tony_tpu.ops.sparse_attention import SparseSpec
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        cfg["torch_dtype"]]
    assumed = {k: v["value"] for k, v in cfg["assumed"].items()
               if isinstance(v, dict)}
    if cfg["lightning_head_dim"] != cfg["head_dim"] \
            or cfg["lightning_nkv"] != cfg["lightning_nh"]:
        raise SystemExit("benchmark: the program's lightning layers have "
                         "the attention layers' head size and as many "
                         "key/value heads as query heads")
    kw = dict(vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
              n_layers=cfg["num_hidden_layers"],
              mixer_types=tuple(cfg["mixer_types"]),
              n_heads=cfg["num_attention_heads"],
              n_kv_heads=cfg["num_key_value_heads"],
              head_dim=cfg["head_dim"], ffn_dim=cfg["intermediate_size"],
              max_seq=cfg["run"]["max_seq"],
              depth_layers=assumed["depth_for_scale"],
              scale_emb=float(cfg["scale_emb"]),
              scale_depth=float(cfg["scale_depth"]),
              dim_model_base=cfg["dim_model_base"],
              norm_eps=float(cfg["rms_norm_eps"]),
              rope_theta=float(cfg["rope_theta"]),
              lightning_heads=cfg["lightning_nh"],
              sparse=SparseSpec(**assumed["sparse_config"]), dtype=dtype)
    kw.update(cfg["run"].get("program", {}))
    return SalaConfig(**kw)


def serving(cfg: dict, seed: int) -> str:
    """Install the configuration into the program as a preset whose
    weights come from the seed; returns the preset's name, the `--config`
    of `tony_tpu.serve.__main__.main`."""
    from tony_tpu.models import sala
    sala.PRESETS["benchmark"] = program_config(cfg)
    program_init = sala.sala_init
    sala.sala_init = lambda c, _key: inproc.seeded_init(program_init, c,
                                                        seed)
    return "benchmark"


def training(cfg: dict, seed: int) -> dict:
    raise SystemExit("benchmark: the minicpm_sala family is served, not "
                     "trained: the program has no training path for layers "
                     "of several kinds (PERF.md section 7)")
