"""The plain reference of the `llama` family (Llama's block, which is
Mistral-7B's): forward pass, loss, gradients and the AdamW update in
straightforward `jax.numpy`, float32 at
`default_matmul_precision("highest")`, with no kernels, no cache and no
batching. It imports nothing of the program and takes nothing the program
made: weights come from the seed by the recipe the configuration file
states (`assumed.weights`), batches and prompts from the benchmark's own
generator.

Departures from the published model, each stated in the configuration
file: the sliding window (4096) is not modelled because no sequence
exceeds it; weights are random. Storage follows the configuration:
parameters and Adam's moments are kept in bfloat16 (norms in float32), and
every computation on them is done in float32.

`precision="fp8"` is the training control: every matmul operand is rounded
through float8_e4m3 with one scale a tensor (straight-through in the
backward pass), the nearest precision below the configuration's bfloat16.
`precision="int8"` is the serving control: every matmul operand is rounded
to int8, one scale a row of the left operand and a column of the right.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32


def jax_seed(seed: int) -> int:
    return int(seed) % 2147483647


def shapes(cfg: dict) -> dict:
    d, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // nh
    return dict(d=d, f=f, v=v, nh=nh, nkv=nkv, hd=hd,
                L=cfg["num_hidden_layers"])


def init_params(cfg: dict, key) -> dict:
    """Seeded weights: embedding N(0,1), matrices N(0, 1/fan_in), drawn in
    float32 with jax's default PRNG and rounded to the stated dtype; norms
    1 in float32. Per-layer matrices are stacked on a leading axis and
    drawn as one array each. `key` is jax.random.PRNGKey(jax_seed(seed))."""
    s = shapes(cfg)
    d, f, v, nh, nkv, hd, L = (s[k] for k in "d f v nh nkv hd L".split())
    dtype = {"bfloat16": jnp.bfloat16, "float32": F32}[cfg["torch_dtype"]]
    k_embed, k_out, k_layers = jax.random.split(key, 3)
    ks = jax.random.split(k_layers, 7)

    def normal(key, shape, scale):
        return (jax.random.normal(key, shape, F32) * scale).astype(dtype)

    si, sf = d ** -0.5, f ** -0.5
    return {
        "embed": normal(k_embed, (v, d), 1.0),
        "layers": {
            "wq": normal(ks[0], (L, d, nh * hd), si),
            "wk": normal(ks[1], (L, d, nkv * hd), si),
            "wv": normal(ks[2], (L, d, nkv * hd), si),
            "wo": normal(ks[3], (L, nh * hd, d), si),
            "w_gate": normal(ks[4], (L, d, f), si),
            "w_up": normal(ks[5], (L, d, f), si),
            "w_down": normal(ks[6], (L, f, d), sf),
            "attn_norm": jnp.ones((L, d), F32),
            "mlp_norm": jnp.ones((L, d), F32),
        },
        "final_norm": jnp.ones((d,), F32),
        "output": normal(k_out, (d, v), si),
    }


def init_on_device(cfg: dict, seed: int) -> dict:
    """`init_params`, one fused jitted call a leaf: drawn whole, the tree
    needs 30 GiB of temporaries on a v5e (the compiler's
    memory_analysis()), a leaf at a time none."""
    key = jax.random.PRNGKey(jax_seed(seed))
    shapes_ = jax.eval_shape(lambda k: init_params(cfg, k), key)
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes_)

    def pick(tree, path):
        for k in path:
            tree = tree[k.key]
        return tree

    # the key is an argument, never a constant the compiler could fold
    leaves = [jax.jit(lambda k, path=path: pick(init_params(cfg, k), path))(
        key) for path, _ in flat]
    return jax.tree_util.tree_unflatten(treedef, leaves)


# -- the block ---------------------------------------------------------------

def _fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale
    return x + lax.stop_gradient(q - x)


def _int8(x, axis):
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True),
                        1e-30) / 127.0
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _mm(a, b, precision):
    if precision == "fp8":
        a, b = _fp8(a), _fp8(b)
    elif precision == "int8":       # a row of a, a column of b: one scale
        a, b = _int8(a, -1), _int8(b, -2)
    return jnp.matmul(a, b, precision=lax.Precision.HIGHEST)


def _rms(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope_tables(cfg: dict, seq: int):
    hd = shapes(cfg)["hd"]
    inv = 1.0 / (float(cfg["rope_theta"])
                 ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.outer(jnp.arange(seq, dtype=F32), inv)
    return jnp.cos(ang), jnp.sin(ang)


def _rope(x, cos, sin):
    """x (heads, S, hd): rotate the pair (x[i], x[i + hd/2]) by the
    position's angle (the half-split convention of the HF implementation)."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate((x1 * cos - x2 * sin, x1 * sin + x2 * cos), -1)


def attention(x, w, cos, sin, cfg: dict, precision="highest"):
    """The attention half of a pre-norm block on one sequence, residual
    included. x (S, d) float32; w one layer's weights in float32. Causal
    softmax attention, one group of query heads (those that share a K/V
    head) at a time so that the S x S scores stay small."""
    s = shapes(cfg)
    nh, nkv, hd, S = s["nh"], s["nkv"], s["hd"], x.shape[0]
    h = _rms(x, w["attn_norm"], float(cfg["rms_norm_eps"]))
    q = _mm(h, w["wq"], precision).reshape(S, nh, hd).transpose(1, 0, 2)
    k = _mm(h, w["wk"], precision).reshape(S, nkv, hd).transpose(1, 0, 2)
    v = _mm(h, w["wv"], precision).reshape(S, nkv, hd).transpose(1, 0, 2)
    q, k = _rope(q, cos, sin), _rope(k, cos, sin)
    mask = jnp.tril(jnp.ones((S, S), bool))

    @jax.checkpoint
    def group(args):
        qg, kg, vg = args                   # (nh/nkv, S, hd), (S, hd) x2
        sc = _mm(qg, kg.T, precision) * (hd ** -0.5)
        p = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
        return _mm(p, vg, precision)

    o = lax.map(group, (q.reshape(nkv, nh // nkv, S, hd), k, v))
    o = o.reshape(nh, S, hd).transpose(1, 0, 2).reshape(S, nh * hd)
    return x + _mm(o, w["wo"], precision)


def block(x, w, cos, sin, cfg: dict, precision="highest"):
    """One pre-norm block on one sequence: `attention`, then the dense
    SwiGLU MLP. x (S, d) float32; w one layer's weights."""
    w = jax.tree.map(lambda a: a.astype(F32), w)
    x = attention(x, w, cos, sin, cfg, precision)
    h = _rms(x, w["mlp_norm"], float(cfg["rms_norm_eps"]))
    ff = jax.nn.silu(_mm(h, w["w_gate"], precision)) * _mm(h, w["w_up"],
                                                          precision)
    return x + _mm(ff, w["w_down"], precision)


def hidden(params, tokens, cfg: dict, precision="highest"):
    """tokens (S,) -> final-normed hidden states (S, d), float32."""
    cos, sin = rope_tables(cfg, tokens.shape[0])
    x = params["embed"][tokens].astype(F32)
    body = jax.checkpoint(
        lambda x, w: (block(x, w, cos, sin, cfg, precision), None))
    x, _ = lax.scan(body, x, params["layers"])
    return _rms(x, params["final_norm"].astype(F32),
                float(cfg["rms_norm_eps"]))


def loss_fn(params, batch_tokens, cfg: dict, precision="highest"):
    """Mean next-token cross entropy over every row and position of
    batch_tokens (B, S+1), one row at a time."""
    @jax.checkpoint
    def row(toks):
        h = hidden(params, toks[:-1], cfg, precision)
        logits = _mm(h, params["output"].astype(F32), precision)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, toks[1:, None], axis=-1)[:, 0]
        return jnp.mean(logz - gold)
    return jnp.mean(lax.map(row, batch_tokens))


# -- AdamW ------------------------------------------------------------------

def learning_rate(opt: dict, count: int) -> float:
    """Linear warm-up from 0 over `warmup_steps`; `count` is the number of
    updates already made. (The cosine decay that follows is never reached
    in the steps the reference takes.)"""
    if count >= opt["warmup_steps"]:
        raise ValueError("the reference only follows warm-up steps")
    return opt["learning_rate"] * count / opt["warmup_steps"]


def adamw_update(params, grads, mu, nu, count: int, opt: dict):
    """One AdamW update, computed in float32 and stored in each leaf's own
    dtype. Returns (params, mu, nu)."""
    b1, b2, eps, wd = opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"]
    lr = learning_rate(opt, count)
    t = count + 1

    def leaf(p, g, m, v):
        pf, gf = p.astype(F32), g.astype(F32)
        m2 = b1 * m.astype(F32) + (1 - b1) * gf
        v2 = b2 * v.astype(F32) + (1 - b2) * gf * gf
        # the moments are stored (rounded) before they are used, as a
        # state kept in the parameters' dtype is
        m2, v2 = m2.astype(p.dtype), v2.astype(p.dtype)
        mh = m2.astype(F32) / (1 - b1 ** t)
        vh = v2.astype(F32) / (1 - b2 ** t)
        u = mh / (jnp.sqrt(vh) + eps) + wd * pf
        return (pf - lr * u).astype(p.dtype), m2, v2

    out = jax.tree.map(leaf, params, grads, mu, nu)
    pick = lambda i: jax.tree.map(lambda o: o[i], out,          # noqa: E731
                                  is_leaf=lambda o: isinstance(o, tuple))
    return pick(0), pick(1), pick(2)


_NORM = jax.jit(lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(F32)))))


def leaf_norms(tree) -> dict:
    flat = jax.tree_util.tree_leaves_with_path(tree)
    name = lambda p: ".".join(str(k.key) for k in p)            # noqa: E731
    return {name(p): float(_NORM(x)) for p, x in flat}


def _free(tree) -> None:
    for x in jax.tree.leaves(tree):
        x.delete()


def follow_training(cfg: dict, batches, seed: int, precision="highest"):
    """Drive the reference through len(batches) optimizer steps. Returns
    the per-step losses, the per-leaf norm and probe projections
    (lib/probe.py) of the first gradient, and the per-leaf norm of the
    parameters' change after the last step.

    Memory (the v5e compiler's memory_analysis(), 5 layers): the gradient
    needs 6.2 GiB of temporaries beside 2.5 GiB each of parameters and
    gradient, so of Adam's two moments only one stays on the device while
    a gradient is computed; the second waits on the host."""
    opt = cfg["run"]["optimizer"]
    params = init_on_device(cfg, seed)
    zeros = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p))
    mu, nu_host = zeros(params), None
    grad = jax.jit(jax.value_and_grad(
        lambda p, b: loss_fn(p, b, cfg, precision)))
    update = jax.jit(lambda p, g, m, v, c: adamw_update(p, g, m, v, c, opt),
                     static_argnums=4, donate_argnums=(0, 2, 3))
    from lib import probe
    losses, grad_norms, grad_proj = [], None, None
    for count, batch in enumerate(batches):
        loss, g = grad(params, jnp.asarray(batch))
        losses.append(float(loss))
        if grad_norms is None:
            grad_norms = leaf_norms(g)
            grad_proj = {n: [float(v) for v in probe.projections(x)]
                         for n, x in zip(grad_norms, jax.tree.leaves(g))}
        nu = zeros(params) if nu_host is None else jax.device_put(nu_host)
        params, mu, nu = update(params, g, mu, nu, count)
        _free(g)
        if count + 1 < len(batches):
            nu_host = jax.device_get(nu)
        _free(nu)
    _free(mu)
    init = init_on_device(cfg, seed)
    diff = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(
        a.astype(F32) - b.astype(F32)))))
    names = leaf_norms(jax.tree.map(lambda x: jnp.zeros(()), params)).keys()
    delta = {n: float(diff(a, b)) for n, a, b in zip(
        names, jax.tree.leaves(params), jax.tree.leaves(init))}
    _free(params)
    _free(init)
    return {"losses": losses, "grad_norms": grad_norms,
            "grad_proj": grad_proj, "delta_norms": delta}


# -- serving ----------------------------------------------------------------

def served_logits(params, tokens, first: int, cfg: dict, pad_to: int = 256,
                  precision: str = "highest"):
    """Logits (float32, highest) at positions first-1 .. len(tokens)-2 of
    one sequence: the reference's prediction for each served token. The
    sequence is padded to a multiple of `pad_to` (causal, so the padding
    changes nothing before it) to bound the number of compiled shapes."""
    n = len(tokens)
    padded = -(-n // pad_to) * pad_to
    toks = jnp.zeros((padded,), jnp.int32).at[:n].set(
        jnp.asarray(tokens, jnp.int32))
    h = _hidden_by_layer(params, toks, cfg, precision)
    rows = h[first - 1:n - 1]
    return _head(rows, params["output"], precision)


@partial(jax.jit, static_argnums=2)
def _head(rows, output, precision):
    return _mm(rows, output.astype(F32), precision)


def _hidden_by_layer(params, toks, cfg: dict, precision="highest"):
    """`hidden` as a Python loop over layers, one compiled block for all
    of them, so that only one layer is ever held in float32."""
    cos, sin = rope_tables(cfg, toks.shape[0])
    x = params["embed"][toks].astype(F32)
    step = _block_jit(_freeze(cfg), precision)
    L = shapes(cfg)["L"]
    for i in range(L):
        w = jax.tree.map(lambda a: a[i], params["layers"])
        x = step(x, w, cos, sin)
    return _rms(x, params["final_norm"].astype(F32),
                float(cfg["rms_norm_eps"]))


_BLOCKS: dict = {}


def _freeze(cfg: dict) -> tuple:
    keys = ("hidden_size", "intermediate_size", "vocab_size",
            "num_attention_heads", "num_key_value_heads",
            "num_hidden_layers", "rms_norm_eps", "rope_theta")
    return tuple((k, cfg[k]) for k in keys)


def _block_jit(frozen: tuple, precision: str):
    if (frozen, precision) not in _BLOCKS:
        cfg = dict(frozen)
        _BLOCKS[frozen, precision] = jax.jit(
            lambda x, w, cos, sin: block(x, w, cos, sin, cfg, precision))
    return _BLOCKS[frozen, precision]
