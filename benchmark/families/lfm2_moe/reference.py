"""The plain reference of the `lfm2_moe` family (LFM2-MoE: gated
short-convolution and GQA attention layers, dense SwiGLU MLPs in the
leading layers and sparse-expert MLPs after them): the forward pass of one
sequence in straightforward `jax.numpy`, float32 at
`default_matmul_precision("highest")`, with no kernels, no cache, no
grouping of rows by expert and no batching. It imports nothing of the
program and takes nothing the program made: weights come from the seed by
the recipe the configuration file states (`assumed.weights`), prompts from
the benchmark's own generator.

The equations (u = RMSNorm(x) with the layer's weight, eps = norm_eps; no
bias anywhere; hd = hidden_size / num_attention_heads):

    x0     = E[token]
    h      = x + Op(RMSNorm(x)),  x' = h + FFN(RMSNorm(h))     every layer
    logits = E RMSNorm(x_last)                        (the head is tied)

`conv` operator (K = conv_L_cache taps): [B, C, z] = W_in u;
    g = B * z;  y_t = C_t * sum_j w_j * g_{t-(K-1)+j}  (g before the
    sequence is 0: tap K-1 multiplies the current position);  W_out y.
`full_attention` operator: q, k, v = W_q u, W_k u, W_v u in heads of hd; q
    and k RMS-normed per head (a weight of hd), rotated (half-split RoPE,
    theta = rope_parameters.rope_theta); causal softmax(q k^T / sqrt(hd)) v
    with one K/V head a group of query heads; W_o.
dense FFN (the first num_dense_layers layers): W2(silu(W1 u) * W3 u).
expert FFN: s = sigmoid(W_r u) (num_experts scores); the
    num_experts_per_tok largest of s + expert_bias are chosen (ties to the
    lower index); their weights are the chosen s themselves, divided by
    their sum + 1e-6 (norm_topk_prob), times routed_scaling_factor; the
    output is sum_e w_e W2_e(silu(W1_e u) * W3_e u). Computed here the
    dense way: every expert multiplies every row and a row's unchosen
    experts are weighted by 0.

**Near-ties of the routing.** A token whose last chosen and first
unchosen expert lie closer in s + expert_bias than the program's
arithmetic can tell apart (its K/V rows and its attention are bfloat16)
picks another expert set there than this float32 reference, and its logits
at that position, and through the short convolutions at the next few,
differ by one expert's whole output. Nothing is set aside for them: the
configuration's `served_logit_gap` limit lies above the widest gap such
tokens reach and below what the int8 control reaches (PERF.md, PR 37).

`precision="int8"` is the serving control: every matmul operand, the
router's too, is rounded to int8, one scale a row of the left operand and
a column of the right. There is no training path (`follow_training`
refuses).
"""

from __future__ import annotations

import json
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32
CONV, ATTENTION = "conv", "full_attention"


def jax_seed(seed: int) -> int:
    return int(seed) % 2147483647


def sizes(cfg: dict) -> dict:
    types, L = list(cfg["layer_types"]), cfg["num_hidden_layers"]
    nd = cfg["num_dense_layers"]
    assert len(types) == L and types[:nd] == [CONV] * nd, types
    at = [i for i, t in enumerate(types) if t == ATTENTION]
    period = at[1] - at[0] if len(at) > 1 else L - nd
    assert types[nd:] == ([ATTENTION] + [CONV] * (period - 1)) \
        * ((L - nd) // period), types
    nh = cfg["num_attention_heads"]
    return dict(d=cfg["hidden_size"], f=cfg["intermediate_size"],
                fe=cfg["moe_intermediate_size"], v=cfg["vocab_size"],
                nh=nh, nkv=cfg["num_key_value_heads"],
                hd=cfg.get("head_dim") or cfg["hidden_size"] // nh,
                E=cfg["num_experts"], k=cfg["num_experts_per_tok"],
                K=cfg["conv_L_cache"], L=L, nd=nd, P=(L - nd) // period,
                R=period - 1)


def init_params(cfg: dict, key) -> dict:
    """Seeded weights: matrices N(0, 1/fan_in), the embedding (which is
    the head) N(0, 1/hidden_size), a conv's taps N(0, 1/taps), drawn in
    float32 with jax's default PRNG and rounded
    to the stated dtype; norms 1 and the routing bias 0 in float32. A
    kind's layers are stacked on leading axes and drawn as one array each.
    The key splits in five (embedding, dense layers, attention layers, the
    periods' conv layers, experts); the dense key in six (W_in, taps,
    W_out, W1, W3, W2), the attention key in four (wq, wk, wv, wo), the
    conv key in three (W_in, taps, W_out), the experts' in four (router,
    W1, W3, W2). `key` is PRNGKey(jax_seed(seed))."""
    s = sizes(cfg)
    d, f, fe, v, nh, nkv, hd, E, K, nd, P, R = (
        s[x] for x in "d f fe v nh nkv hd E K nd P R".split())
    Lm = s["L"] - nd
    dtype = {"bfloat16": jnp.bfloat16, "float32": F32}[cfg["torch_dtype"]]
    k_embed, k_dense, k_attn, k_conv, k_moe = jax.random.split(key, 5)

    def normal(key, shape, scale):
        return (jax.random.normal(key, shape, F32) * scale).astype(dtype)

    def conv_op(keys, *lead):
        return {"w_in": normal(keys[0], lead + (d, 3 * d), d ** -0.5),
                "w_conv": normal(keys[1], lead + (K, d), K ** -0.5),
                "w_out": normal(keys[2], lead + (d, d), d ** -0.5),
                "op_norm": jnp.ones(lead + (d,), F32),
                "ffn_norm": jnp.ones(lead + (d,), F32)}

    kd, ka = jax.random.split(k_dense, 6), jax.random.split(k_attn, 4)
    kc, km = jax.random.split(k_conv, 3), jax.random.split(k_moe, 4)
    return {
        "embed": normal(k_embed, (v, d), d ** -0.5),
        "dense": {**conv_op(kd[:3], nd),
                  "w1": normal(kd[3], (nd, d, f), d ** -0.5),
                  "w3": normal(kd[4], (nd, d, f), d ** -0.5),
                  "w2": normal(kd[5], (nd, f, d), f ** -0.5)},
        "attn": {"wq": normal(ka[0], (P, d, nh * hd), d ** -0.5),
                 "wk": normal(ka[1], (P, d, nkv * hd), d ** -0.5),
                 "wv": normal(ka[2], (P, d, nkv * hd), d ** -0.5),
                 "wo": normal(ka[3], (P, nh * hd, d), (nh * hd) ** -0.5),
                 "q_norm": jnp.ones((P, hd), F32),
                 "k_norm": jnp.ones((P, hd), F32),
                 "op_norm": jnp.ones((P, d), F32),
                 "ffn_norm": jnp.ones((P, d), F32)},
        "conv": conv_op(kc, P, R),
        "moe": {"router": normal(km[0], (Lm, d, E), d ** -0.5),
                "expert_bias": jnp.zeros((Lm, E), F32),
                "w1": normal(km[1], (Lm, E, d, fe), d ** -0.5),
                "w3": normal(km[2], (Lm, E, d, fe), d ** -0.5),
                "w2": normal(km[3], (Lm, E, fe, d), fe ** -0.5)},
        "final_norm": jnp.ones((d,), F32),
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
    hd = sizes(cfg)["hd"]
    inv = 1.0 / (float(cfg["rope_parameters"]["rope_theta"])
                 ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.outer(jnp.arange(seq, dtype=F32), inv)
    return jnp.cos(ang), jnp.sin(ang)


# -- operators ---------------------------------------------------------------

def conv_operator(h, w, cfg: dict, precision):
    """The gated short convolution on one sequence. h (S, d) the normed
    input."""
    K, S = cfg["conv_L_cache"], h.shape[0]
    b, c, z = jnp.split(_mm(h, w["w_in"], precision), 3, axis=-1)
    g = jnp.pad(b * z, ((K - 1, 0), (0, 0)))
    y = c * sum(w["w_conv"][j] * g[j:j + S] for j in range(K))
    return _mm(y, w["w_out"], precision)


def attention_operator(h, w, cos, sin, cfg: dict, precision):
    """Causal GQA attention on one sequence, one group of query heads at
    a time so that the S x S scores stay small."""
    s = sizes(cfg)
    nh, nkv, hd, S = s["nh"], s["nkv"], s["hd"], h.shape[0]
    eps = float(cfg["norm_eps"])
    q = _mm(h, w["wq"], precision).reshape(S, nh, hd).transpose(1, 0, 2)
    k = _mm(h, w["wk"], precision).reshape(S, nkv, hd).transpose(1, 0, 2)
    v = _mm(h, w["wv"], precision).reshape(S, nkv, hd).transpose(1, 0, 2)
    q = _rope(_rms(q, w["q_norm"], eps), cos, sin)
    k = _rope(_rms(k, w["k_norm"], eps), cos, sin)
    mask = jnp.tril(jnp.ones((S, S), bool))

    def group(args):
        qg, kg, vg = args                   # (nh/nkv, S, hd), (S, hd) x2
        sc = _mm(qg, kg.T, precision) * (hd ** -0.5)
        p = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
        return _mm(p, vg, precision)

    o = lax.map(group, (q.reshape(nkv, nh // nkv, S, hd), k, v))
    o = o.reshape(nh, S, hd).transpose(1, 0, 2).reshape(S, nh * hd)
    return _mm(o, w["wo"], precision)


def dense_ffn(h, w, precision):
    return _mm(jax.nn.silu(_mm(h, w["w1"], precision))
               * _mm(h, w["w3"], precision), w["w2"], precision)


def routing(h, router, bias, cfg: dict, precision):
    """gate (S, E): a row's weight of every expert, 0 for the unchosen."""
    k, norm = cfg["num_experts_per_tok"], cfg["norm_topk_prob"]
    scores = jax.nn.sigmoid(_mm(h, router, precision))
    chosen = jnp.argsort(-(scores + bias), axis=-1, stable=True)[:, :k]
    weight = jnp.take_along_axis(scores, chosen, axis=-1)
    if norm:
        weight = weight / (jnp.sum(weight, axis=-1, keepdims=True) + 1e-6)
    weight = weight * float(cfg["routed_scaling_factor"])
    rows = jnp.arange(h.shape[0])[:, None]
    return jnp.zeros_like(scores).at[rows, chosen].set(weight)


def expert_ffn(h, w, cfg: dict, precision):
    """The expert MLP the dense way: every expert on every row, weighted
    by the row's gate. w holds ONE layer's router, bias and experts in
    their stored type. Returns the output (S, d)."""
    gate = routing(h, w["router"].astype(F32),
                           w["expert_bias"].astype(F32), cfg, precision)

    def one(acc, xs):
        w1, w3, w2, g = xs
        y = _mm(jax.nn.silu(_mm(h, w1.astype(F32), precision))
                * _mm(h, w3.astype(F32), precision), w2.astype(F32),
                precision)
        return acc + g[:, None] * y, None

    out, _ = lax.scan(one, jnp.zeros_like(h),
                      (w["w1"], w["w3"], w["w2"], gate.T))
    return out


# -- the model ---------------------------------------------------------------

def layer(x, w, experts, cos, sin, cfg: dict, kind: str, precision):
    """One layer on one sequence: x (S, d) float32; w the layer's operator
    weights, norms and (a dense layer) MLP; experts None or the layer's
    router and experts. Returns x'."""
    eps = float(cfg["norm_eps"])
    w = jax.tree.map(lambda a: a.astype(F32), w)
    h = _rms(x, w["op_norm"], eps)
    if kind == CONV:
        x = x + conv_operator(h, w, cfg, precision)
    else:
        x = x + attention_operator(h, w, cos, sin, cfg, precision)
    h = _rms(x, w["ffn_norm"], eps)
    if experts is None:
        return x + dense_ffn(h, w, precision)
    return x + expert_ffn(h, experts, cfg, precision)


_LAYERS: dict = {}


def _freeze(cfg: dict) -> str:
    keep = {k: v for k, v in cfg.items()
            if k not in ("run", "limits", "stands_for", "changed", "why",
                         "assumed")}
    return json.dumps(keep, sort_keys=True)


def _layer_jit(cfg: dict, kind: str, precision: str):
    """One compiled function a kind of layer body, shared by its layers,
    so that only one layer is ever held in float32."""
    key = (_freeze(cfg), kind, precision)
    if key not in _LAYERS:
        _LAYERS[key] = jax.jit(
            lambda x, w, experts, cos, sin: layer(
                x, w, experts, cos, sin, cfg, kind, precision),
            donate_argnums=0)
    return _LAYERS[key]


def layers_of(params, cfg: dict):
    """(kind, the layer's weights, its experts or None) in the order the
    layers run."""
    s = sizes(cfg)
    at = lambda tree, *i: jax.tree.map(lambda a: a[i], tree)  # noqa: E731
    for i in range(s["nd"]):
        yield CONV, at(params["dense"], i), None
    m = 0
    for p in range(s["P"]):
        yield ATTENTION, at(params["attn"], p), at(params["moe"], m)
        m += 1
        for r in range(s["R"]):
            yield CONV, at(params["conv"], p, r), at(params["moe"], m)
            m += 1


def hidden_states(params, toks, cfg: dict, precision="highest"):
    """toks (S,) -> the final-normed hidden states (S, d) float32."""
    cos, sin = rope_tables(cfg, toks.shape[0])
    x = params["embed"][toks].astype(F32)
    for kind, w, experts in layers_of(params, cfg):
        x = _layer_jit(cfg, kind, precision)(x, w, experts, cos, sin)
    return _rms(x, params["final_norm"].astype(F32), float(cfg["norm_eps"]))


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
    h = hidden_states(params, toks, cfg, precision)
    return _head(h[first - 1:n - 1], params["embed"], precision)


@partial(jax.jit, static_argnums=2)
def _head(rows, embed, precision):
    return _mm(rows, embed.astype(F32).T, precision)


def follow_training(cfg: dict, batches, seed: int, precision="highest"):
    raise NotImplementedError(
        "the lfm2_moe family is served, not trained: the program's expert "
        "layer without dropped tokens has no training path (PERF.md "
        "section 7)")
