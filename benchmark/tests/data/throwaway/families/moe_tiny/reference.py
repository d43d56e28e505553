"""The plain reference of the throwaway family: Llama's attention (taken
from the `llama` family's reference, as are the precisions, the AdamW
update and the norms) around a router and experts of its own. Every token
goes to its `num_experts_per_tok` most probable experts, weighted by their
probabilities rescaled to sum to 1, and none is dropped. Tiny sizes only:
every expert is computed for every token and the weights are drawn whole.
It imports nothing of the program."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from families.llama import reference as base
from lib import probe

F32 = jnp.float32


def init_params(cfg: dict, key) -> dict:
    """The llama family's draw for everything but the MLP; router N(0,
    1/d) and the experts' matrices N(0, 1/fan_in), stacked over layers."""
    d, f, L = (cfg["hidden_size"], cfg["intermediate_size"],
               cfg["num_hidden_layers"])
    E = cfg["num_local_experts"]
    k_base, k_router, k_experts = jax.random.split(key, 3)
    params = base.init_params(cfg, k_base)
    ks = jax.random.split(k_experts, 3)
    layers = {k: v for k, v in params["layers"].items()
              if k not in ("w_gate", "w_up", "w_down")}

    def normal(key, shape, scale):
        return (jax.random.normal(key, shape, F32) * scale).astype(
            params["embed"].dtype)

    layers.update(router=normal(k_router, (L, d, E), d ** -0.5),
                  we_gate=normal(ks[0], (L, E, d, f), d ** -0.5),
                  we_up=normal(ks[1], (L, E, d, f), d ** -0.5),
                  we_down=normal(ks[2], (L, E, f, d), f ** -0.5))
    return dict(params, layers=layers)


def init_on_device(cfg: dict, seed: int) -> dict:
    return jax.jit(lambda k: init_params(cfg, k))(
        jax.random.PRNGKey(base.jax_seed(seed)))


def experts(h, w, cfg: dict, precision):
    """h (S, d) -> (S, d): the chosen experts' SwiGLU outputs, weighted."""
    E, top_k = cfg["num_local_experts"], cfg["num_experts_per_tok"]
    probs = jax.nn.softmax(base._mm(h, w["router"], "highest"), axis=-1)
    vals, idx = lax.top_k(probs, top_k)
    gates = jnp.sum(jax.nn.one_hot(idx, E, dtype=F32)
                    * (vals / jnp.sum(vals, -1, keepdims=True))[..., None],
                    axis=1)                                     # (S, E)
    out = jnp.zeros_like(h)
    for e in range(E):
        ff = (jax.nn.silu(base._mm(h, w["we_gate"][e], precision))
              * base._mm(h, w["we_up"][e], precision))
        out = out + gates[:, e:e + 1] * base._mm(ff, w["we_down"][e],
                                                 precision)
    return out


def hidden(params, tokens, cfg: dict, precision="highest"):
    cos, sin = base.rope_tables(cfg, tokens.shape[0])
    eps = float(cfg["rms_norm_eps"])
    x = params["embed"][tokens].astype(F32)
    for i in range(cfg["num_hidden_layers"]):
        w = jax.tree.map(lambda a: a[i].astype(F32), params["layers"])
        x = base.attention(x, w, cos, sin, cfg, precision)
        x = x + experts(base._rms(x, w["mlp_norm"], eps), w, cfg, precision)
    return base._rms(x, params["final_norm"].astype(F32), eps)


def logits(params, tokens, cfg: dict, precision="highest"):
    return base._mm(hidden(params, tokens, cfg, precision),
                    params["output"].astype(F32), precision)


def loss_fn(params, batch_tokens, cfg: dict, precision="highest"):
    def row(toks):
        lg = logits(params, toks[:-1], cfg, precision)
        gold = jnp.take_along_axis(lg, toks[1:, None], axis=-1)[:, 0]
        return jnp.mean(jax.nn.logsumexp(lg, axis=-1) - gold)
    return jnp.mean(lax.map(row, batch_tokens))


def follow_training(cfg: dict, batches, seed: int, precision="highest"):
    opt = cfg["run"]["optimizer"]
    first = params = init_on_device(cfg, seed)
    mu = nu = jax.tree.map(jnp.zeros_like, params)
    grad = jax.jit(jax.value_and_grad(
        lambda p, b: loss_fn(p, b, cfg, precision)))
    losses, grad_norms, grad_proj = [], None, None
    for count, batch in enumerate(batches):
        loss, g = grad(params, jnp.asarray(batch))
        losses.append(float(loss))
        if grad_norms is None:
            grad_norms = base.leaf_norms(g)
            grad_proj = {n: [float(v) for v in probe.projections(x)]
                         for n, x in zip(grad_norms, jax.tree.leaves(g))}
        params, mu, nu = base.adamw_update(params, g, mu, nu, count, opt)
    delta = base.leaf_norms(jax.tree.map(jnp.subtract, params, first))
    return {"losses": losses, "grad_norms": grad_norms,
            "grad_proj": grad_proj, "delta_norms": delta}


def served_logits(params, tokens, first: int, cfg: dict, pad_to: int = 256,
                  precision: str = "highest"):
    n = len(tokens)
    toks = jnp.zeros((-(-n // pad_to) * pad_to,), jnp.int32).at[:n].set(
        jnp.asarray(tokens, jnp.int32))
    return jax.jit(lambda p, t: logits(p, t, cfg, precision))(
        params, toks)[first - 1:n - 1]
