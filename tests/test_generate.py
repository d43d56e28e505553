"""KV-cache generation (models/generate.py) vs the no-cache oracle: greedy
decode must match re-running the full training forward on the growing
sequence exactly (tiny config is f32 end to end)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from tony_tpu.models.generate import (
    decode_step, generate, new_cache_rows, prefill, write_cache_rows,
)
from tony_tpu.models.llama import get_config, llama_forward, llama_init


def _setup(seed=0, b=2, p=8):
    cfg = get_config("tiny")
    params = llama_init(cfg, jax.random.PRNGKey(seed))
    prompt = jax.random.randint(jax.random.PRNGKey(seed + 1), (b, p), 0,
                                cfg.vocab_size, jnp.int32)
    return cfg, params, prompt


def _oracle_greedy(params, cfg, prompt, n):
    """No-cache reference: full forward over the growing sequence."""
    seq = prompt
    out = []
    for _ in range(n):
        logits = llama_forward(params, seq, cfg)
        tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        out.append(tok)
        seq = jnp.concatenate([seq, tok[:, None]], axis=1)
    return jnp.stack(out, axis=1)                          # (B, N)


def test_greedy_generate_matches_oracle():
    cfg, params, prompt = _setup()
    n = 6
    got = generate(params, cfg, prompt, n)
    want = _oracle_greedy(params, cfg, prompt, n)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_prefill_logits_match_forward():
    cfg, params, prompt = _setup()
    logits, cache = prefill(params, prompt, cfg, cache_len=16)
    full = llama_forward(params, prompt, cfg)[:, -1]
    np.testing.assert_allclose(np.asarray(logits), np.asarray(full),
                               atol=2e-5, rtol=2e-5)
    # prompt K/V written, padding rows zero
    assert cache["k"].shape[3] == 16
    assert not np.allclose(np.asarray(cache["k"][:, :, :, :8]), 0.0)
    np.testing.assert_array_equal(
        np.asarray(cache["k"][:, :, :, 8:]), 0.0)


def test_decode_step_matches_forward_next_position():
    """One cached decode step == the full forward's logits at that spot."""
    cfg, params, prompt = _setup()
    logits, cache = prefill(params, prompt, cfg, cache_len=16)
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    step_logits, _ = decode_step(params, cfg, cache, tok, jnp.int32(8))
    seq = jnp.concatenate([prompt, tok[:, None]], axis=1)
    want = llama_forward(params, seq, cfg)[:, -1]
    np.testing.assert_allclose(np.asarray(step_logits), np.asarray(want),
                               atol=3e-5, rtol=3e-5)


def test_eos_latches():
    """Once eos is emitted the rest of the row is eos."""
    cfg, params, prompt = _setup()
    want = _oracle_greedy(params, cfg, prompt, 8)
    eos = int(np.asarray(want)[0, 2])   # force an 'eos' mid-stream
    got = np.asarray(generate(params, cfg, prompt, 8, eos_id=eos))
    row = got[0]
    hits = np.where(row == eos)[0]
    assert hits.size, "chosen eos never emitted?"
    first = hits[0]
    assert (row[first:] == eos).all()


def test_sampled_generation_valid_and_reproducible():
    cfg, params, prompt = _setup()
    k = jax.random.PRNGKey(7)
    a = generate(params, cfg, prompt, 5, temperature=0.8, top_k=4, key=k)
    b = generate(params, cfg, prompt, 5, temperature=0.8, top_k=4, key=k)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert ((np.asarray(a) >= 0) & (np.asarray(a) < cfg.vocab_size)).all()


def test_top_p_nucleus_semantics():
    """top_p truncation: only tokens inside the smallest prefix whose
    probability mass reaches top_p can ever be sampled, the most
    probable token always survives, and top_p=1.0 is exactly the
    untruncated distribution."""
    from tony_tpu.models.generate import _sample

    probs = jnp.array([[0.5, 0.3, 0.15, 0.05]])
    logits = jnp.log(probs)
    # mass 0.6 -> keep {0 (cum-p=0), 1 (cum-p=0.5)}; 2 (0.8) is out
    seen = {int(_sample(logits, 1.0, 0, jax.random.PRNGKey(i),
                        top_p=0.6)[0]) for i in range(64)}
    assert seen <= {0, 1} and 1 in seen, seen
    # a tiny mass keeps only the argmax — sampling degenerates to greedy
    seen = {int(_sample(logits, 1.0, 0, jax.random.PRNGKey(i),
                        top_p=1e-6)[0]) for i in range(16)}
    assert seen == {0}, seen
    # top_p=0 (CLI-reachable) must degrade to the argmax too, never to
    # a fully-masked row that categorical samples uniformly
    seen = {int(_sample(logits, 1.0, 0, jax.random.PRNGKey(i),
                        top_p=0.0)[0]) for i in range(16)}
    assert seen == {0}, seen
    # top_p=1.0 is a no-op: identical draws to the plain path per key
    for i in range(8):
        k = jax.random.PRNGKey(100 + i)
        assert int(_sample(logits, 1.0, 0, k, top_p=1.0)[0]) == \
            int(_sample(logits, 1.0, 0, k)[0])
    # end-to-end through generate(): reproducible and in-range
    cfg, params, prompt = _setup()
    k = jax.random.PRNGKey(8)
    a = generate(params, cfg, prompt, 5, temperature=0.9, top_p=0.8,
                 key=k)
    b = generate(params, cfg, prompt, 5, temperature=0.9, top_p=0.8,
                 key=k)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert ((np.asarray(a) >= 0) & (np.asarray(a) < cfg.vocab_size)).all()


def test_generate_budget_guard():
    cfg, params, prompt = _setup()
    import pytest
    with pytest.raises(ValueError, match="exceeds"):
        generate(params, cfg, prompt, cfg.max_seq)


def test_generate_text_ragged_prompts_unaffected_by_batchmates():
    """Ragged prompts are grouped by length: a short prompt's output must
    equal generating it alone (no pad-token contamination)."""
    cfg, params, _ = _setup()

    class IdTok:
        def encode(self, s):
            return [int(c) % cfg.vocab_size for c in s.encode()]

        def decode(self, ids):
            return ",".join(str(i) for i in ids)

    from tony_tpu.models.generate import generate_text

    tok = IdTok()
    short, long_ = "ab", "abcdefgh"
    together = generate_text(params, cfg, [short, long_], tok,
                             max_new_tokens=4)
    alone = generate_text(params, cfg, [short], tok, max_new_tokens=4)
    assert together[0] == alone[0]


def test_generate_on_tp_mesh_matches_single_device():
    """Greedy decode with tp/fsdp-sharded params under an ambient mesh
    must produce the same tokens as the unsharded path (serving-style
    sharded inference; XLA inserts the collectives from shardings)."""
    from tony_tpu.models.llama import llama_param_axes
    from tony_tpu.parallel import make_mesh, plan_mesh, shard_pytree

    cfg, params, prompt = _setup()
    want = generate(params, cfg, prompt, 6)
    mesh = make_mesh(plan_mesh(8, tp=2))
    sharded = shard_pytree(params, llama_param_axes(cfg), mesh)
    with jax.set_mesh(mesh):
        got = generate(sharded, cfg, prompt, 6)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# the decode step against a plain reference: the layer loop only reads the
# cache, the new rows are written in place after it (PR 27)
# ---------------------------------------------------------------------------

def _seed_write_cache_rows(kc, vc, scales, k, v, offsets):
    """What the cache held after the write the decode step made before PR
    27 (per layer, a vmapped per-row dynamic_update_slice; an int8 cache
    quantizes the rows first) — the plain reference of the row format."""
    from tony_tpu.models.quant import quantize_rows

    def row_update(cache_row, new_row, off):
        return lax.dynamic_update_slice_in_dim(cache_row, new_row, off,
                                               axis=1)

    if scales is None:
        return (jax.vmap(row_update)(kc, k.astype(kc.dtype), offsets),
                jax.vmap(row_update)(vc, v.astype(vc.dtype), offsets), None)
    qk, k_s = quantize_rows(k)
    qv, v_s = quantize_rows(v)
    return (jax.vmap(row_update)(kc, qk, offsets),
            jax.vmap(row_update)(vc, qv, offsets),
            (jax.vmap(row_update)(scales[0], k_s, offsets),
             jax.vmap(row_update)(scales[1], v_s, offsets)))


def _garbage_cache(key, shape, kind):
    """A cache full of stale values: whatever a step does not write must
    come back bit-equal, and whatever lies at or beyond a slot's position
    must not reach its logits."""
    kk, kv, ks = jax.random.split(key, 3)
    if kind == "int8":
        cache = {n: jax.random.randint(k, shape, -127, 128, jnp.int8)
                 for n, k in (("k", kk), ("v", kv))}
        for n, k in (("k_scale", ks), ("v_scale", kk)):
            cache[n] = jax.random.uniform(k, shape[:-1] + (1,), jnp.float32,
                                          0.01, 0.05)
        return cache
    dtype = jnp.bfloat16 if kind == "bf16" else jnp.float32
    return {n: jax.random.normal(k, shape, dtype)
            for n, k in (("k", kk), ("v", kv))}


@pytest.mark.parametrize("w", [1, 3])
@pytest.mark.parametrize("kind", ["f32", "bf16", "int8"])
def test_row_write_stores_what_the_slab_write_stored(kind, w):
    """new_cache_rows + write_cache_rows (one in-place write of all layers'
    rows after the layer loop) leave the cache bit-equal to the per-layer
    slab write they replace: same row format, same positions (0 and the
    last that fits included), nothing else touched."""
    n_layers, b, g, s, hd = 3, 4, 2, 16, 8
    cache = _garbage_cache(jax.random.PRNGKey(0), (n_layers, b, g, s, hd),
                           kind)
    quant = kind == "int8"
    k, v = (jax.random.normal(key, (n_layers, b, g, w, hd), jnp.float32)
            for key in jax.random.split(jax.random.PRNGKey(1)))
    offsets = jnp.asarray([0, s - w, 5, 9], jnp.int32)

    rows = [new_cache_rows(k[i], v[i], cache["k"].dtype, quant)
            for i in range(n_layers)]
    stacked = {name: jnp.stack([r[0][name] for r in rows]) for name in cache}
    got = write_cache_rows(cache, stacked, offsets)
    for i in range(n_layers):
        scales = (cache["k_scale"][i], cache["v_scale"][i]) if quant else None
        kc, vc, sc = _seed_write_cache_rows(cache["k"][i], cache["v"][i],
                                            scales, k[i], v[i], offsets)
        want = {"k": kc, "v": vc}
        if quant:
            want["k_scale"], want["v_scale"] = sc
        for name, arr in want.items():
            np.testing.assert_array_equal(np.asarray(got[name][i]),
                                          np.asarray(arr), err_msg=name)
        # the attention-ready view is what a read back would dequantize to
        _, k_eff, _ = rows[i]
        stored = got["k"][i, 2, :, 5:5 + w]
        if quant:
            stored = stored.astype(jnp.float32) * got["k_scale"][i, 2, :,
                                                                 5:5 + w]
        np.testing.assert_array_equal(np.asarray(k_eff[2]),
                                      np.asarray(stored))


def _family(name):
    """(config, params, full forward -> logits) of one model family."""
    if name == "moe":
        from tony_tpu.models.moe import get_moe_config, moe_forward, moe_init
        cfg = get_moe_config("moe_tiny", capacity_factor=4 / 2)
        return (cfg, moe_init(cfg, jax.random.PRNGKey(0)),
                lambda p, seq: moe_forward(p, seq, cfg)[0])
    cfg = get_config("tiny")
    return (cfg, llama_init(cfg, jax.random.PRNGKey(0)),
            lambda p, seq: llama_forward(p, seq, cfg))


@pytest.mark.parametrize("family", ["dense", "moe"])
@pytest.mark.parametrize("kind", ["f32", "bf16", "int8"])
def test_decode_step_at_staggered_positions_against_the_forward(kind, family):
    """Continuous batching's shape: every slot at its own position, 0 and
    budget-1 included, in a cache whose other rows hold stale garbage.
    Each slot's logits are the full forward's on ITS sequence, every cache
    entry not at (slot, pos) comes back bit-equal, and the rows written
    are the seed's format of that token's K/V (checked at layer 0, whose
    input no attention has touched)."""
    from tony_tpu.models.llama import embed_lookup, qkv_proj, rope_tables
    from tony_tpu.ops.rmsnorm import rms_norm
    from tony_tpu.ops.rope import apply_rope

    cfg, params, forward = _family(family)
    budget, quant = 16, kind == "int8"
    pos = np.asarray([0, 5, budget - 1, 9], np.int32)
    b = len(pos)
    seqs = [jax.random.randint(jax.random.PRNGKey(10 + i), (int(p) + 1,), 0,
                               cfg.vocab_size, jnp.int32)
            for i, p in enumerate(pos)]
    cache = _garbage_cache(
        jax.random.PRNGKey(3),
        (cfg.n_layers, b, cfg.n_kv_heads, budget, cfg.head_dim), kind)
    for i, p in enumerate(pos):
        if p == 0:
            continue
        _, pc = prefill(params, seqs[i][None, :p], cfg, cache_len=budget,
                        quant_cache=quant)
        for name in cache:
            cache[name] = cache[name].at[:, i, :, :p].set(
                pc[name][:, 0, :, :p].astype(cache[name].dtype))
    tok = jnp.stack([s[-1] for s in seqs])

    logits, new = decode_step(params, cfg, cache, tok, jnp.asarray(pos))

    want = jnp.stack([forward(params, s[None, :])[0, -1] for s in seqs])
    if kind == "f32":
        np.testing.assert_allclose(np.asarray(logits), np.asarray(want),
                                   atol=3e-5, rtol=3e-5)
    else:
        # the lossy caches: tests/test_quant.py's bound on the whole step
        rmse = float(jnp.sqrt(jnp.mean((logits - want) ** 2))
                     / jnp.sqrt(jnp.mean(want ** 2)))
        assert rmse < 0.05, rmse
    # the scalar-pos form is the per-row form at equal positions
    scalar = decode_step(params, cfg, cache, tok, jnp.int32(5))
    per_row = decode_step(params, cfg, cache, tok, jnp.full((b,), 5,
                                                            jnp.int32))
    for got, ref in zip(jax.tree.leaves(scalar), jax.tree.leaves(per_row)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))

    written = np.zeros((b, budget), bool)
    written[np.arange(b), pos] = True
    for name in cache:
        old, got = np.asarray(cache[name]), np.asarray(new[name])
        assert got.dtype == old.dtype and got.shape == old.shape
        keep = np.broadcast_to(~written[None, :, None, :, None], old.shape)
        np.testing.assert_array_equal(got[keep], old[keep], err_msg=name)

    layer0 = jax.tree.map(lambda a: a[0], params["layers"])
    cos, sin = rope_tables(cfg, budget)
    x = embed_lookup(params["embed"], tok[:, None], cfg)
    _, k, v = qkv_proj(rms_norm(x, layer0["attn_norm"], cfg.norm_eps),
                       layer0, cfg)
    k = apply_rope(k, cos, sin, positions=jnp.asarray(pos)[:, None])
    # the rows as the seed's slab write stores them (the row format itself
    # is pinned bit for bit above; here k and v are recomputed op by op
    # outside the step's fused loop body, which may move a float32 ulp —
    # so one rounding step of the cache's type is allowed)
    scales = (cache["k_scale"][0], cache["v_scale"][0]) if quant else None
    kc, vc, sc = _seed_write_cache_rows(cache["k"][0], cache["v"][0], scales,
                                        k, v, jnp.asarray(pos))
    at = (np.arange(b), slice(None), pos)
    for name, ref, ref_scale in (("k", kc, sc and sc[0]),
                                 ("v", vc, sc and sc[1])):
        got = np.asarray(new[name][0].astype(jnp.float32))[at]
        ref = np.asarray(ref.astype(jnp.float32))[at]
        if quant:
            got_scale = np.asarray(new[name + "_scale"][0])[at]
            np.testing.assert_allclose(got_scale, np.asarray(ref_scale)[at],
                                       rtol=1e-6)
            assert np.abs(got - ref).max() <= 1, name
        else:
            np.testing.assert_allclose(
                got, ref, atol=1e-6, rtol=2 ** -7 if kind == "bf16" else 1e-6)


@pytest.mark.parametrize("dtype", ["f32", "bf16"])
@pytest.mark.parametrize("b,w", [(4, 1), (1, 37)], ids=["decode-4x1",
                                                        "admission-1x37"])
def test_serving_projection_is_the_trainers_bit_for_bit(b, w, dtype):
    """`qkv_proj(split_on_result=True)`, what `window_logits`, `prefill` and
    `kvcache.prefill_suffix` call, against the trainer's `qkv_proj`: the
    same three einsums with a barrier on their results, so the same q, k
    and v to the last bit, jitted as the serving programs are — at a decode
    step's shape and at an admission's. (On a TPU the barrier also rounds
    the Q and K products to the weights' type before RoPE, as the source
    says, where the folded matmul kept them in float32: PERF.md, PR 42.)"""
    from dataclasses import replace

    from tony_tpu.models.llama import qkv_proj

    cfg = get_config("tiny")
    if dtype == "bf16":
        cfg = replace(cfg, dtype=jnp.bfloat16)
    layer0 = jax.tree.map(lambda a: a[0],
                          llama_init(cfg, jax.random.PRNGKey(0))["layers"])
    h = jax.random.normal(jax.random.PRNGKey(1), (b, w, cfg.dim),
                          jnp.float32).astype(cfg.dtype)
    want = jax.jit(lambda h, l: qkv_proj(h, l, cfg))(h, layer0)
    got = jax.jit(lambda h, l: qkv_proj(h, l, cfg, split_on_result=True))(
        h, layer0)
    shapes = [(b, n, w, cfg.head_dim)
              for n in (cfg.n_heads, cfg.n_kv_heads, cfg.n_kv_heads)]
    assert [g.shape for g in got] == shapes
    for g, r in zip(got, want):
        assert g.dtype == r.dtype == cfg.dtype
        np.testing.assert_array_equal(np.asarray(g.astype(jnp.float32)),
                                      np.asarray(r.astype(jnp.float32)))
