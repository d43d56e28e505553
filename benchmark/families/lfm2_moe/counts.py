"""Operations and bytes the `lfm2_moe` family's algorithm needs (gated
short-convolution and GQA attention operators; a dense SwiGLU MLP in the
leading layers, `num_experts_per_tok` of `num_experts` expert MLPs after
them; the head tied to the embedding), computed from shapes alone.

A matmul of (m, k) x (k, n) is 2*m*k*n operations. Every count here is the
LEAST the algorithm needs, so that no share of a roofline can read over
100 %. What a decode step reads depends on the routing: an expert's
weights are needed once if any riding token chose it, so the step's bytes
are a function of how many experts were hit, which the program counts on
the device (`moe_experts_hit_total`); a caller that does not say reads the
least there can be. `cfg` is a configuration file's dict with the source's
key names.
"""

from __future__ import annotations

CONV, ATTENTION = "conv", "full_attention"


def head_dim(cfg: dict) -> int:
    return cfg.get("head_dim") or (cfg["hidden_size"]
                                   // cfg["num_attention_heads"])


def layers(cfg: dict) -> dict:
    kinds = list(cfg["layer_types"])
    nd = cfg["num_dense_layers"]
    return {"conv": kinds.count(CONV), "attention": kinds.count(ATTENTION),
            "dense": nd, "expert": len(kinds) - nd}


def operator_params(cfg: dict, kind: str) -> int:
    """Matmul weights of one operator (norms and the conv's taps take part
    in no matmul)."""
    d, hd = cfg["hidden_size"], head_dim(cfg)
    if kind == CONV:
        return d * 3 * d + d * d
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return d * nh * hd + 2 * d * nkv * hd + nh * hd * d


def expert_params(cfg: dict) -> int:
    """One expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def shared_matmul_params(cfg: dict) -> int:
    """Every weight EVERY token is multiplied by: the operators, the dense
    layers' MLPs, the routers and the head (the embedding's transpose; the
    lookup itself is no matmul)."""
    n = layers(cfg)
    d = cfg["hidden_size"]
    return (n["conv"] * operator_params(cfg, CONV)
            + n["attention"] * operator_params(cfg, ATTENTION)
            + n["dense"] * 3 * d * cfg["intermediate_size"]
            + n["expert"] * d * cfg["num_experts"]
            + d * cfg["vocab_size"])


def total_params(cfg: dict) -> int:
    n = layers(cfg)
    d, hd = cfg["hidden_size"], head_dim(cfg)
    small = (n["conv"] * (cfg["conv_L_cache"] * d + 2 * d)
             + n["attention"] * (2 * hd + 2 * d)
             + n["expert"] * cfg["num_experts"] + d)
    return (shared_matmul_params(cfg) + small
            + n["expert"] * cfg["num_experts"] * expert_params(cfg))


# -- serving: resident bytes and a decode step's reads ----------------------

STATE_ITEMSIZE = 4          # the conv state is float32, whatever else


def conv_state_bytes(cfg: dict, slots: int) -> int:
    return (layers(cfg)["conv"] * slots * cfg["conv_L_cache"]
            * cfg["hidden_size"] * STATE_ITEMSIZE)


def cache_bytes(cfg: dict, slots: int, budget: int, itemsize: int = 2) -> int:
    """The cache by layer kind: K and V rows of the attention layers alone
    in `itemsize`, and every conv layer's last conv_L_cache gated inputs in
    float32."""
    kv = (2 * layers(cfg)["attention"] * slots
          * cfg["num_key_value_heads"] * budget * head_dim(cfg) * itemsize)
    return kv + conv_state_bytes(cfg, slots)


def decode_step_bytes(cfg: dict, context_lengths, itemsize: int = 2,
                      experts_hit: float | None = None,
                      riders: float | None = None) -> float:
    """Bytes one decode step must read: every shared matmul weight once;
    `experts_hit` experts' weights in each expert layer (the mean the
    program counted; absent: the fewest a step can read, one rider's
    num_experts_per_tok); the K and V rows of the context in flight in the
    attention layers; each rider's conv states read and written (`riders`;
    absent: one a context)."""
    n = layers(cfg)
    if experts_hit is None:
        experts_hit = cfg["num_experts_per_tok"]
    if riders is None:
        riders = len(context_lengths)
    kv_row = 2 * n["attention"] * cfg["num_key_value_heads"] \
        * head_dim(cfg) * itemsize
    return (shared_matmul_params(cfg) * itemsize
            + n["expert"] * experts_hit * expert_params(cfg) * itemsize
            + kv_row * float(sum(context_lengths))
            + 2.0 * riders * conv_state_bytes(cfg, 1))


# -- the grouped matmul of one expert layer ---------------------------------

def expert_layer_flops(cfg: dict, tokens: float) -> float:
    """The three matmuls of num_experts_per_tok experts for each token."""
    return 2.0 * tokens * cfg["num_experts_per_tok"] * expert_params(cfg)


def expert_layer_bytes(cfg: dict, tokens: float, experts_hit: float,
                       itemsize: int = 2) -> float:
    """The hit experts' weights once in `itemsize`; each routed row read
    once (hidden), its gated product written and read (expert width) and
    its result written once (hidden), all float32."""
    rows = tokens * cfg["num_experts_per_tok"]
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    return (experts_hit * expert_params(cfg) * itemsize
            + rows * 4 * (d + 2 * f + d))


# -- not of this family ------------------------------------------------------

def train_flops_per_token(cfg: dict, seq: int) -> float:
    raise ValueError("the lfm2_moe family is served, not trained")


def flash_call_flops(cfg: dict, seq: int, batch: int, kernel: str) -> float:
    raise ValueError("the lfm2_moe family is served, not trained")


def flash_call_bytes(cfg: dict, seq: int, batch: int, kernel: str,
                     itemsize: int = 2) -> float:
    raise ValueError("the lfm2_moe family is served, not trained")
