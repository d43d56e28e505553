"""Operations and bytes the `llama` family's algorithm needs (one kind of
layer: GQA attention with a full cache and a dense SwiGLU MLP), computed
from shapes alone.

These are the benchmark's own counts (the program's
`LlamaConfig.flops_per_token` counts the embedding lookup as a matmul and
is not used). A matmul of (m, k) x (k, n) is 2*m*k*n operations. Recomputed
(rematerialised) operations are never counted. `cfg` is a configuration
file's dict with the source's key names.
"""

from __future__ import annotations


def head_dim(cfg: dict) -> int:
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]


def layer_matmul_params(cfg: dict) -> int:
    """Weights of one block that take part in a matmul (norms do not)."""
    d, f, hd = cfg["hidden_size"], cfg["intermediate_size"], head_dim(cfg)
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return d * nh * hd + 2 * d * nkv * hd + nh * hd * d + 3 * d * f


def matmul_params(cfg: dict) -> int:
    """Every weight a token is multiplied by: the blocks and the output
    head. The embedding table is a lookup, not a matmul."""
    return (cfg["num_hidden_layers"] * layer_matmul_params(cfg)
            + cfg["hidden_size"] * cfg["vocab_size"])


def total_params(cfg: dict) -> int:
    d = cfg["hidden_size"]
    return (matmul_params(cfg) + cfg["vocab_size"] * d
            + cfg["num_hidden_layers"] * 2 * d + d)


def attention_fwd_flops(cfg: dict, seq: int, batch: int = 1,
                        causal: bool = True) -> float:
    """QK^T and PV over all heads of ONE layer, forward: 4*S*S*hd a head,
    halved under a causal mask."""
    f = 4.0 * batch * cfg["num_attention_heads"] * seq * seq * head_dim(cfg)
    return f / 2 if causal else f


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward + backward (3x forward) of the matmuls and of causal
    attention, per token of a sequence of `seq`. No embedding lookup, no
    recompute."""
    attn = cfg["num_hidden_layers"] * attention_fwd_flops(cfg, seq) / seq
    return 3.0 * (2.0 * matmul_params(cfg) + attn)


# -- flash attention kernels (one call = all heads of one layer) -----------

def flash_call_flops(cfg: dict, seq: int, batch: int, kernel: str) -> float:
    """Operations one call of a flash kernel needs. fwd: QK^T and PV.
    bwd_dq: recomputes QK^T, then dP = dO V^T and dQ = dS K (3 matmuls of
    2*S*S*hd). bwd_dkv: recomputes QK^T, dP = dO V^T, dV = P^T dO and
    dK = dS^T Q (4). Halved by causality. The recomputed QK^T is part of the
    algorithm (flash never stores P), so it counts here, unlike remat."""
    per_matmul = attention_fwd_flops(cfg, seq, batch) / 2.0
    return per_matmul * {"fwd": 2, "bwd_dq": 3, "bwd_dkv": 4}[kernel]


def flash_call_bytes(cfg: dict, seq: int, batch: int, kernel: str,
                     itemsize: int = 2) -> float:
    """Bytes one call must move at least: each operand read once and each
    result written once (K and V in their narrow GQA layout)."""
    hd, nh, nkv = head_dim(cfg), cfg["num_attention_heads"], cfg["num_key_value_heads"]
    q = batch * nh * seq * hd * itemsize
    kv = batch * nkv * seq * hd * itemsize
    lse = batch * nh * seq * 4
    if kernel == "fwd":                 # read q,k,v; write o, lse
        return q + 2 * kv + q + lse
    if kernel == "bwd_dq":              # read q,k,v,o,do,lse; write dq
        return 3 * q + 2 * kv + lse + q
    if kernel == "bwd_dkv":             # read q,k,v,o,do,lse; write dk,dv
        return 3 * q + 2 * kv + lse + 2 * kv
    raise KeyError(kernel)


# -- serving ----------------------------------------------------------------

def decode_step_bytes(cfg: dict, context_lengths, itemsize: int = 2) -> float:
    """Bytes one decode step must read: every matmul weight once, and the
    K and V rows of the context each busy slot has so far."""
    hd, nkv = head_dim(cfg), cfg["num_key_value_heads"]
    kv_row = 2 * cfg["num_hidden_layers"] * nkv * hd * itemsize
    return matmul_params(cfg) * itemsize + kv_row * float(sum(context_lengths))


def cache_bytes(cfg: dict, slots: int, budget: int, itemsize: int = 2) -> int:
    return (2 * cfg["num_hidden_layers"] * slots * cfg["num_key_value_heads"]
            * budget * head_dim(cfg) * itemsize)
