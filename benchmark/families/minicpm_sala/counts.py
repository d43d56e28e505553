"""Operations and bytes the `minicpm_sala` family's algorithm needs (two
kinds of layer: `minicpm4` block-sparse attention over a K/V cache with
compressed keys, `lightning-attn` linear attention over a recurrent
state; a dense SwiGLU MLP in both), computed from shapes alone.

A matmul of (m, k) x (k, n) is 2*m*k*n operations. Every count here is the
LEAST the algorithm needs, so that no share of a roofline can read over
100 %: a kernel that walks more key blocks than a query attends to, or
moves a row twice, reads under its roofline, not over it. `cfg` is a
configuration file's dict with the source's key names; sizes the source
does not give are under its `assumed`.
"""

from __future__ import annotations

SPARSE, LIGHTNING = "minicpm4", "lightning-attn"
STATE_ITEMSIZE = 4          # the lightning state is float32, whatever else


def sparse_config(cfg: dict) -> dict:
    return cfg["assumed"]["sparse_config"]["value"]


def layers(cfg: dict) -> tuple:
    """(sparse layers, lightning layers)."""
    kinds = list(cfg["mixer_types"])
    return kinds.count(SPARSE), kinds.count(LIGHTNING)


def layer_matmul_params(cfg: dict, kind: str) -> int:
    """Weights of one layer that take part in a matmul (norms do not)."""
    d, f, hd = cfg["hidden_size"], cfg["intermediate_size"], cfg["head_dim"]
    mlp = 3 * d * f
    if kind == SPARSE:
        nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
        # q, k, v, o and the output gate
        return d * nh * hd + 2 * d * nkv * hd + nh * hd * d + d * nh * hd \
            + mlp
    lh, ld = cfg["lightning_nh"], cfg["lightning_head_dim"]
    return 3 * d * lh * ld + lh * ld * d + d * lh * ld + mlp   # q,k,v,o,gate


def matmul_params(cfg: dict) -> int:
    """Every weight a token is multiplied by: the layers and the output
    head. The embedding table is a lookup, not a matmul."""
    ns, nl = layers(cfg)
    return (ns * layer_matmul_params(cfg, SPARSE)
            + nl * layer_matmul_params(cfg, LIGHTNING)
            + cfg["hidden_size"] * cfg["vocab_size"])


# -- serving: resident bytes and a decode step's reads ----------------------

def state_bytes(cfg: dict, slots: int) -> int:
    _, nl = layers(cfg)
    ld = cfg["lightning_head_dim"]
    return nl * slots * cfg["lightning_nh"] * ld * ld * STATE_ITEMSIZE


def cache_bytes(cfg: dict, slots: int, budget: int, itemsize: int = 2) -> int:
    """The cache by layer kind: K and V rows, the compressed keys (one a
    kernel_stride of the budget) and the ring of the last kernel_size K
    rows of every sparse layer, in `itemsize`; the float32 state of every
    lightning layer."""
    ns, _ = layers(cfg)
    sc = sparse_config(cfg)
    row = cfg["num_key_value_heads"] * cfg["head_dim"] * itemsize
    per_slot = (2 * budget + budget // sc["kernel_stride"]
                + sc["kernel_size"]) * row
    return ns * slots * per_slot + state_bytes(cfg, slots)


def decode_step_bytes(cfg: dict, context_lengths, itemsize: int = 2) -> float:
    """Bytes one decode step must read (and, for the states, write): every
    matmul weight once; for each open slot the compressed keys of its
    context and, of every sparse layer, the K and V rows it attends to
    (its whole context up to dense_len, topk blocks past it), and each
    lightning state read and written."""
    ns, _ = layers(cfg)
    sc = sparse_config(cfg)
    row = cfg["num_key_value_heads"] * cfg["head_dim"] * itemsize
    total = float(matmul_params(cfg) * itemsize)
    for ctx in context_lengths:
        attended = ctx if ctx <= sc["dense_len"] else min(
            ctx, sc["topk"] * sc["block_size"])
        total += ns * row * (ctx / sc["kernel_stride"] + 2 * attended)
        total += 2 * state_bytes(cfg, 1)
    return total


# -- the block-sparse prefill kernel (all its calls of one layer) -----------

def attended_pairs(cfg: dict, seq: int) -> int:
    """(query, key) pairs one head attends to over a prompt of `seq`
    tokens past dense_len: query t has t // block + 1 blocks behind it,
    attends to at most topk of them, and to its own only up to itself."""
    sc = sparse_config(cfg)
    blk, topk = sc["block_size"], sc["topk"]
    pairs = 0
    for t0 in range(0, seq, blk):       # a block of queries at a time
        rows = min(blk, seq - t0)
        others = min(t0 // blk + 1, topk) - 1
        pairs += rows * others * blk + rows * (rows + 1) // 2
    return pairs


def sparse_call_flops(cfg: dict, seq: int) -> float:
    """QK^T and PV over exactly the attended pairs, all heads of ONE
    layer's prefill (the selection stage is not the kernel's)."""
    return 4.0 * cfg["num_attention_heads"] * cfg["head_dim"] \
        * attended_pairs(cfg, seq)


def sparse_call_bytes(cfg: dict, seq: int, itemsize: int = 2) -> float:
    """Q read and O written once, each K and V row read once."""
    hd = cfg["head_dim"]
    q = seq * cfg["num_attention_heads"] * hd * itemsize
    kv = seq * cfg["num_key_value_heads"] * hd * itemsize
    return 2.0 * q + 2.0 * kv


# -- the chunked lightning kernel (one call = one layer's prefill) ----------

def lightning_call_flops(cfg: dict, seq: int) -> float:
    """The recurrence itself, the least form: per token and head the
    update k^T v into the decayed state (3 d^2: the decay, the product,
    the sum) and the read q S (2 d^2)."""
    ld = cfg["lightning_head_dim"]
    return 5.0 * seq * cfg["lightning_nh"] * ld * ld


def lightning_call_bytes(cfg: dict, seq: int, itemsize: int = 2) -> float:
    """Q, K and V read and O written once; the final state written."""
    lh, ld = cfg["lightning_nh"], cfg["lightning_head_dim"]
    return 4.0 * seq * lh * ld * itemsize + lh * ld * ld * STATE_ITEMSIZE


# -- not of this family ------------------------------------------------------

def train_flops_per_token(cfg: dict, seq: int) -> float:
    raise ValueError("the minicpm_sala family is served, not trained")


def flash_call_flops(cfg: dict, seq: int, batch: int, kernel: str) -> float:
    raise ValueError("the minicpm_sala family is served, not trained")


def flash_call_bytes(cfg: dict, seq: int, batch: int, kernel: str,
                     itemsize: int = 2) -> float:
    raise ValueError("the minicpm_sala family is served, not trained")
