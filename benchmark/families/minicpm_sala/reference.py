"""The plain reference of the `minicpm_sala` family (MiniCPM-SALA: layers
of two kinds, learned block-sparse attention and lightning linear
attention): the forward pass of one sequence in straightforward
`jax.numpy`, float32 at `default_matmul_precision("highest")`, with no
kernels, no cache, no chunked form and no batching. It imports nothing of
the program and takes nothing the program made: weights come from the seed
by the recipe the configuration file states (`assumed.weights`), prompts
from the benchmark's own generator. Sizes the source's config.json does
not give are read from the configuration file's `assumed` (each with its
origin there).

The equations (x a row of the sequence, t its position, c = scale_depth /
sqrt(assumed.depth_for_scale), eps = rms_norm_eps):

    x0     = scale_emb * E[token]
    h      = x + c * Mixer(RMSNorm(x))              every layer
    x'     = h + c * W_down(silu(W_gate n) * W_up n),  n = RMSNorm(h)
    logits = W_head RMSNorm(x_last) / (hidden_size / dim_model_base)

`minicpm4` mixer (H query heads in G groups of R = H / G, one K/V head a
group, head size d; no bias, no positions): q, k RMS-normed per head.
With n the context the token was computed in (the prompt's length for a
prompt token, t + 1 for a served one):

    n <= dense_len:  causal softmax(q k^T / sqrt(d)) v
    n >  dense_len:  Kbar_j = mean(k[stride*j : stride*j + kernel]) for the
                     windows complete inside [0, t];
                     s_h = softmax_j(q_h . Kbar_j / sqrt(d));
                     S_g = sum of s_h over the group's heads;
                     score(b) = max of S_g over the Kbar_j whose window
                     overlaps block b = [block*b, block*(b+1));
                     always taken: blocks < init_blocks, and the blocks
                     from the one holding t - window_size + 1 to the one
                     holding t; attended: the topk best blocks including
                     those (ties to the lower index), causal softmax over
                     exactly their tokens.
    then o * sigmoid(W_g RMSNorm(x)), then W_o.

`lightning-attn` mixer (H heads of size d): q, k RMS-normed per head, both
rotated (half-split RoPE, theta = rope_theta), q / sqrt(d);

    S_t = lam_h S_{t-1} + k_t^T v_t,   o_t = q_t S_t      (S float32 d x d)
    lam_h = exp(-slope_h),  slope_h = 2^(-8 (h+1) / H) * (1 - l/(L-1) + 1e-5)

with l the layer's index among all L layers as run; then RMSNorm(o) per
head, o * sigmoid(W_z RMSNorm(x)), then W_o.

`precision="int8"` is the serving control: every matmul operand is rounded
to int8, one scale a row of the left operand and a column of the right
(the operands of the recurrence's two products, q, k and v, a row each).
There is no training path (`follow_training` refuses).
"""

from __future__ import annotations

import json
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32
SPARSE, LIGHTNING = "minicpm4", "lightning-attn"
ROWS = 2048             # rows of the sequence the MLP takes at once
QUERY_BLOCK = 64        # queries the sparse layer's scores are held for


def jax_seed(seed: int) -> int:
    return int(seed) % 2147483647


def assumed(cfg: dict, key: str):
    return cfg["assumed"][key]["value"]


def sizes(cfg: dict) -> dict:
    types = list(cfg["mixer_types"])
    L = cfg["num_hidden_layers"]
    assert len(types) == L, (len(types), L)
    period = next((i for i in range(1, L) if types[i] == SPARSE), L)
    assert types == ([SPARSE] + [LIGHTNING] * (period - 1)) * (L // period)
    return dict(d=cfg["hidden_size"], f=cfg["intermediate_size"],
                v=cfg["vocab_size"], nh=cfg["num_attention_heads"],
                nkv=cfg["num_key_value_heads"], hd=cfg["head_dim"],
                lh=cfg["lightning_nh"], L=L, P=L // period, R=period - 1)


def init_params(cfg: dict, key) -> dict:
    """Seeded weights: embedding N(0,1), matrices N(0, 1/fan_in), drawn in
    float32 with jax's default PRNG and rounded to the stated dtype; norms
    1 in float32. A kind's layers are stacked on leading axes (periods for
    the sparse layers, periods x R for the lightning layers) and drawn as
    one array each: the key splits in four (embedding, head, sparse,
    lightning), and a kind's key in eight (wq, wk, wv, wo, the output
    gate, w_gate, w_up, w_down). `key` is PRNGKey(jax_seed(seed))."""
    s = sizes(cfg)
    d, f, v, nh, nkv, hd, lh, P, R = (s[k] for k in
                                      "d f v nh nkv hd lh P R".split())
    dtype = {"bfloat16": jnp.bfloat16, "float32": F32}[cfg["torch_dtype"]]
    k_embed, k_out, k_sparse, k_light = jax.random.split(key, 4)
    ks, kl = jax.random.split(k_sparse, 8), jax.random.split(k_light, 8)

    def normal(key, shape, scale):
        return (jax.random.normal(key, shape, F32) * scale).astype(dtype)

    si, sf = d ** -0.5, f ** -0.5
    return {
        "embed": normal(k_embed, (v, d), 1.0),
        "sparse": {
            "wq": normal(ks[0], (P, d, nh * hd), si),
            "wk": normal(ks[1], (P, d, nkv * hd), si),
            "wv": normal(ks[2], (P, d, nkv * hd), si),
            "wo": normal(ks[3], (P, nh * hd, d), (nh * hd) ** -0.5),
            "w_og": normal(ks[4], (P, d, nh * hd), si),
            "w_gate": normal(ks[5], (P, d, f), si),
            "w_up": normal(ks[6], (P, d, f), si),
            "w_down": normal(ks[7], (P, f, d), sf),
            "q_norm": jnp.ones((P, hd), F32),
            "k_norm": jnp.ones((P, hd), F32),
            "attn_norm": jnp.ones((P, d), F32),
            "mlp_norm": jnp.ones((P, d), F32),
        },
        "lightning": {
            "wq": normal(kl[0], (P, R, d, lh * hd), si),
            "wk": normal(kl[1], (P, R, d, lh * hd), si),
            "wv": normal(kl[2], (P, R, d, lh * hd), si),
            "wo": normal(kl[3], (P, R, lh * hd, d), (lh * hd) ** -0.5),
            "w_og": normal(kl[4], (P, R, d, lh * hd), si),
            "w_gate": normal(kl[5], (P, R, d, f), si),
            "w_up": normal(kl[6], (P, R, d, f), si),
            "w_down": normal(kl[7], (P, R, f, d), sf),
            "q_norm": jnp.ones((P, R, hd), F32),
            "k_norm": jnp.ones((P, R, hd), F32),
            "o_norm": jnp.ones((P, R, hd), F32),
            "attn_norm": jnp.ones((P, R, d), F32),
            "mlp_norm": jnp.ones((P, R, d), F32),
        },
        "final_norm": jnp.ones((d,), F32),
        "output": normal(k_out, (d, v), si),
    }


def init_on_device(cfg: dict, seed: int) -> dict:
    """`init_params`, one fused jitted call a leaf (drawn whole, the tree
    needs tens of GB of temporaries; a leaf at a time none)."""
    key = jax.random.PRNGKey(jax_seed(seed))
    shapes_ = jax.eval_shape(lambda k: init_params(cfg, k), key)
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes_)

    def pick(tree, path):
        for k in path:
            tree = tree[k.key]
        return tree

    leaves = [jax.jit(lambda k, path=path: pick(init_params(cfg, k), path))(
        key) for path, _ in flat]
    return jax.tree_util.tree_unflatten(treedef, leaves)


# -- pieces ------------------------------------------------------------------

def _int8(x, axis):
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True),
                        1e-30) / 127.0
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _mm(a, b, precision):
    if precision == "int8":         # a row of a, a column of b: one scale
        a, b = _int8(a, -1), _int8(b, -2)
    return jnp.matmul(a, b, precision=lax.Precision.HIGHEST)


def _rms(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, cos, sin):
    """x (heads, S, hd): rotate the pair (x[i], x[i + hd/2]) by the
    position's angle (the half-split convention)."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate((x1 * cos - x2 * sin, x1 * sin + x2 * cos), -1)


def rope_tables(cfg: dict, seq: int):
    hd = cfg["lightning_head_dim"]
    inv = 1.0 / (float(cfg["rope_theta"])
                 ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.outer(jnp.arange(seq, dtype=F32), inv)
    return jnp.cos(ang), jnp.sin(ang)


def depth_scale(cfg: dict) -> float:
    return float(cfg["scale_depth"]) / float(
        assumed(cfg, "depth_for_scale")) ** 0.5


def slope(cfg: dict, layer: int):
    """(heads,) decay slopes of the lightning layer at index `layer`
    among all layers as run."""
    H, L = cfg["lightning_nh"], cfg["num_hidden_layers"]
    base = 2.0 ** (-8.0 * (jnp.arange(H, dtype=F32) + 1.0) / H)
    return base * (1.0 - layer / max(L - 1, 1) + 1e-5)


def _by_rows(fn, x, rows: int = ROWS):
    """fn over the rows of x, `rows` at a time."""
    n = x.shape[0]
    if n <= rows:
        return fn(x)
    pad = (-n) % rows
    xs = jnp.pad(x, ((0, pad), (0, 0))).reshape(-1, rows, x.shape[1])
    return lax.map(fn, xs).reshape(-1, x.shape[1])[:n]


def mlp_half(h, w, cfg: dict, precision):
    """h + c * MLP(RMSNorm(h)), row block by row block."""
    c, eps = depth_scale(cfg), float(cfg["rms_norm_eps"])

    def rows(hr):
        n = _rms(hr, w["mlp_norm"], eps)
        ff = jax.nn.silu(_mm(n, w["w_gate"], precision)) \
            * _mm(n, w["w_up"], precision)
        return hr + c * _mm(ff, w["w_down"], precision)

    return _by_rows(rows, h)


# -- the minicpm4 layer ------------------------------------------------------

def block_choice(q, kbar, pos, cfg: dict, precision):
    """Which blocks each query attends to. q (G, R, T, hd) at positions
    pos (T,); kbar (G, NC, hd). Returns (G, T, NB) bool, NB = ceil of the
    sequence over block_size (as many as kbar's NC allows: NC = 4 NB)."""
    sc = assumed(cfg, "sparse_config")
    ks, st, blk = sc["kernel_size"], sc["kernel_stride"], sc["block_size"]
    hd = q.shape[-1]
    nc = kbar.shape[1]
    nb = nc // 4
    s = _mm(q, jnp.swapaxes(kbar, -1, -2)[:, None], precision) * hd ** -0.5
    j = jnp.arange(nc)
    complete = (st * j[None, :] + ks) <= (pos[:, None] + 1)     # (T, NC)
    s = jnp.where(complete, s, -jnp.inf)
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.where(complete, jnp.exp(s - jnp.where(jnp.isfinite(m), m, 0.0)),
                  0.0)
    p = e / jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)
    group = jnp.sum(p, axis=1)                                  # (G, T, NC)
    # block b overlaps the windows j = 4b-1 .. 4b+3 (a window is 2
    # strides, a block 4): the largest of those five
    shifted = jnp.pad(group, ((0, 0), (0, 0), (1, 4)),
                      constant_values=-jnp.inf)
    score = jnp.max(jnp.stack(
        [shifted[..., i::4][..., :nb] for i in range(5)]), axis=0)
    b = jnp.arange(nb)
    here = pos // blk
    window_from = jnp.maximum(pos - sc["window_size"] + 1, 0) // blk
    always = (b[None, :] < sc["init_blocks"]) \
        | (b[None, :] >= window_from[:, None])
    score = jnp.where(always, jnp.inf, score)
    causal = b[None, :] <= here[:, None]
    score = jnp.where(causal, score, -jnp.inf)
    order = jnp.argsort(-score, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)
    return (rank < sc["topk"]) & causal


def sparse_mixer(h, w, first, cfg: dict, precision):
    """The minicpm4 mixer on one sequence. h (S, d) the normed input;
    `first` the prompt's length: a position below it was computed in a
    context of `first` tokens, a later one in its own t + 1."""
    s = sizes(cfg)
    nh, nkv, hd, S = s["nh"], s["nkv"], s["hd"], h.shape[0]
    sc = assumed(cfg, "sparse_config")
    ks, st, blk = sc["kernel_size"], sc["kernel_stride"], sc["block_size"]
    eps = float(cfg["rms_norm_eps"])
    q = _mm(h, w["wq"], precision).reshape(S, nh, hd).transpose(1, 0, 2)
    k = _mm(h, w["wk"], precision).reshape(S, nkv, hd).transpose(1, 0, 2)
    v = _mm(h, w["wv"], precision).reshape(S, nkv, hd).transpose(1, 0, 2)
    q, k = _rms(q, w["q_norm"], eps), _rms(k, w["k_norm"], eps)
    nb = -(-S // blk)
    # every window of `kernel` rows at stride `stride`, 4 a block; those
    # that run past the sequence are never complete for any query
    kp = jnp.pad(k, ((0, 0), (0, st * 4 * nb + ks - S), (0, 0)))
    at = st * jnp.arange(4 * nb)[:, None] + jnp.arange(ks)[None, :]
    kbar = jnp.mean(kp[:, at], axis=2)                      # (G, 4NB, hd)
    qb = min(QUERY_BLOCK, S)
    pad = (-S) % qb
    qg = jnp.pad(q, ((0, 0), (0, pad), (0, 0))).reshape(
        nkv, nh // nkv, -1, qb, hd)
    key_pos = jnp.arange(S)

    def rows(args):
        qs, t0 = args                                       # (G, R, qb, hd)
        pos = t0 + jnp.arange(qb)
        context = jnp.where(pos < first, first, pos + 1)
        chosen = block_choice(qs, kbar, pos, cfg, precision)
        chosen = chosen[..., key_pos // blk]                # (G, qb, S)
        chosen = jnp.where((context > sc["dense_len"])[None, :, None],
                           chosen, True)
        mask = chosen & (key_pos[None, :] <= pos[:, None])[None]
        a = _mm(qs, jnp.swapaxes(k, -1, -2)[:, None], precision) \
            * hd ** -0.5
        p = jax.nn.softmax(jnp.where(mask[:, None], a, -jnp.inf), axis=-1)
        return _mm(p, v[:, None], precision)                # (G, R, qb, hd)

    starts = qb * jnp.arange(qg.shape[2])
    o = lax.map(rows, (jnp.moveaxis(qg, 2, 0), starts))     # (nq,G,R,qb,hd)
    o = jnp.moveaxis(o, 0, 2).reshape(nh, -1, hd)[:, :S]
    o = o.transpose(1, 0, 2).reshape(S, nh * hd)
    o = o * jax.nn.sigmoid(_mm(h, w["w_og"], precision))
    return _mm(o, w["wo"], precision)


# -- the lightning layer -----------------------------------------------------

def lightning_mixer(h, w, slopes, cos, sin, cfg: dict, precision):
    """The lightning-attn mixer on one sequence, by its recurrence, one
    token at a time. h (S, d) the normed input; slopes (heads,)."""
    H, hd, S = cfg["lightning_nh"], cfg["lightning_head_dim"], h.shape[0]
    eps = float(cfg["rms_norm_eps"])

    def heads(name):
        return _mm(h, w[name], precision).reshape(S, H, hd).transpose(
            1, 0, 2)

    q = _rope(_rms(heads("wq"), w["q_norm"], eps), cos, sin) * hd ** -0.5
    k = _rope(_rms(heads("wk"), w["k_norm"], eps), cos, sin)
    v = heads("wv")
    if precision == "int8":     # the recurrence's products k^T v and q S
        q, k, v = _int8(q, -1), _int8(k, -1), _int8(v, -1)
    lam = jnp.exp(-slopes)[:, None, None]

    def step(state, qkv):
        qt, kt, vt = qkv                                    # (H, hd) each
        state = lam * state + kt[:, :, None] * vt[:, None, :]
        return state, jnp.sum(qt[:, :, None] * state, axis=1)

    _, o = lax.scan(step, jnp.zeros((H, hd, hd), F32),
                    (q.transpose(1, 0, 2), k.transpose(1, 0, 2),
                     v.transpose(1, 0, 2)))                 # (S, H, hd)
    o = _rms(o, w["o_norm"], eps).reshape(S, H * hd)
    o = o * jax.nn.sigmoid(_mm(h, w["w_og"], precision))
    return _mm(o, w["wo"], precision)


# -- the model ---------------------------------------------------------------

def sparse_layer(x, w, first, cfg: dict, precision="highest"):
    w = jax.tree.map(lambda a: a.astype(F32), w)
    h = _rms(x, w["attn_norm"], float(cfg["rms_norm_eps"]))
    x = x + depth_scale(cfg) * sparse_mixer(h, w, first, cfg, precision)
    return mlp_half(x, w, cfg, precision)


def lightning_layer(x, w, slopes, cos, sin, cfg: dict,
                    precision="highest"):
    w = jax.tree.map(lambda a: a.astype(F32), w)
    h = _rms(x, w["attn_norm"], float(cfg["rms_norm_eps"]))
    x = x + depth_scale(cfg) * lightning_mixer(h, w, slopes, cos, sin, cfg,
                                               precision)
    return mlp_half(x, w, cfg, precision)


_LAYERS: dict = {}


def _freeze(cfg: dict) -> str:
    keep = {k: v for k, v in cfg.items()
            if k not in ("run", "limits", "stands_for", "changed", "why")}
    return json.dumps(keep, sort_keys=True)


def _layer_jits(cfg: dict, precision: str):
    """One compiled function a kind, shared by all its layers, so that
    only one layer is ever held in float32."""
    key = (_freeze(cfg), precision)
    if key not in _LAYERS:
        _LAYERS[key] = (
            jax.jit(lambda x, w, first: sparse_layer(
                x, w, first, cfg, precision), donate_argnums=0),
            jax.jit(lambda x, w, sl, cos, sin: lightning_layer(
                x, w, sl, cos, sin, cfg, precision), donate_argnums=0))
    return _LAYERS[key]


def hidden_states(params, toks, first: int, cfg: dict,
                  precision="highest"):
    """toks (S,) -> final-normed hidden states (S, d), float32; `first`
    the prompt's length (see `sparse_mixer`)."""
    s = sizes(cfg)
    cos, sin = rope_tables(cfg, toks.shape[0])
    x = params["embed"][toks].astype(F32) * float(cfg["scale_emb"])
    sparse, lightning = _layer_jits(cfg, precision)
    first = jnp.int32(first)
    for p in range(s["P"]):
        x = sparse(x, jax.tree.map(lambda a: a[p], params["sparse"]), first)
        for r in range(s["R"]):
            layer = p * (s["R"] + 1) + 1 + r
            x = lightning(
                x, jax.tree.map(lambda a: a[p, r], params["lightning"]),
                slope(cfg, layer), cos, sin)
    return _rms(x, params["final_norm"].astype(F32),
                float(cfg["rms_norm_eps"]))


def served_logits(params, tokens, first: int, cfg: dict, pad_to: int = 256,
                  precision: str = "highest"):
    """Logits (float32, highest) at positions first-1 .. len(tokens)-2 of
    one sequence: the reference's prediction for each served token. The
    sequence is padded to a multiple of `pad_to` (everything is causal, so
    the padding changes nothing before it) to bound the compiled shapes."""
    n = len(tokens)
    padded = -(-n // pad_to) * pad_to
    toks = jnp.zeros((padded,), jnp.int32).at[:n].set(
        jnp.asarray(tokens, jnp.int32))
    h = hidden_states(params, toks, first, cfg, precision)
    rows = h[first - 1:n - 1]
    return _head(rows, params["output"], precision) / (
        cfg["hidden_size"] / cfg["dim_model_base"])


@partial(jax.jit, static_argnums=2)
def _head(rows, output, precision):
    return _mm(rows, output.astype(F32), precision)


def follow_training(cfg: dict, batches, seed: int, precision="highest"):
    raise NotImplementedError(
        "the minicpm_sala family is served, not trained: the program has "
        "no training path for layers of several kinds (PERF.md section 7)")
