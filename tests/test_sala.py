"""The hybrid of sparse-attention and lightning linear-attention layers
(models/sala.py, ops/sparse_attention.py, ops/lightning.py) against the
benchmark's plain reference of the family
(benchmark/families/minicpm_sala/reference.py: float32, no kernels, no
cache, no chunked form), on the CPU at small widths: `dense_len` 64, blocks
of 8 tokens, compressed keys of 4 every 2, top-6 blocks of which 1 initial
and 2-3 of the window, two periods of one sparse + three lightning layers.
"""

import importlib
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tony_tpu.models import sala
from tony_tpu.ops import lightning, sparse_attention as sa
from tony_tpu.serve.engine import ContinuousBatchingEngine

# (tony_tpu.models exports the function `generate` over the module's name)
gen = importlib.import_module("tony_tpu.models.generate")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = sala.get_sala_config("sala_tiny")
SPARSE = dict(kernel_size=4, kernel_stride=2, init_blocks=1, block_size=8,
              window_size=16, topk=6, dense_len=64)
# the same model in the source's key names, as a configuration file has it
CFG = {
    "hidden_size": 64, "intermediate_size": 128, "vocab_size": 256,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "lightning_nh": 4, "lightning_nkv": 4, "lightning_head_dim": 16,
    "num_hidden_layers": 8, "mixer_types": list(CONFIG.mixer_types),
    "rms_norm_eps": 1e-6, "rope_theta": 10000, "scale_emb": 12,
    "scale_depth": 1.4, "dim_model_base": 16, "torch_dtype": "float32",
    "assumed": {"depth_for_scale": {"value": 8},
                "sparse_config": {"value": SPARSE}},
}
SEED, BUDGET = 5, 256
# (prompt tokens, served tokens): below, across and far above dense_len
REQUESTS = {"below": (40, 10), "across": (58, 14), "above": (100, 12),
            "far-above": (200, 9)}
TOL = 2e-5          # float32 rounding at logits of order 1


@pytest.fixture(scope="module")
def reference():
    spec = importlib.util.spec_from_file_location(
        "sala_reference", os.path.join(
            ROOT, "benchmark", "families", "minicpm_sala", "reference.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def params():
    with jax.default_matmul_precision("highest"):
        return sala.sala_init(CONFIG, jax.random.PRNGKey(SEED))


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(0)
    return {name: rng.integers(0, 256, p).tolist()
            for name, (p, _) in REQUESTS.items()}


@pytest.fixture(scope="module")
def served(params, prompts):
    """Every request through the engine, three slots for four requests:
    they are admitted at staggered steps and decode at their own
    positions, the last into a recycled slot."""
    with jax.default_matmul_precision("highest"):
        engine = ContinuousBatchingEngine(params, CONFIG, n_slots=3,
                                          token_budget=BUDGET)
        handles = {}
        for name, (_, new) in REQUESTS.items():
            handles[name] = engine.submit(prompts[name], new)
            engine.step()
        while engine.step():
            pass
        return ({name: h.result(timeout=1) for name, h in handles.items()},
                engine.snapshot())


def test_the_reference_draws_the_programs_weights(reference, params):
    theirs = reference.init_on_device(CFG, SEED)
    ours, theirs = jax.tree.leaves(params), jax.tree.leaves(theirs)
    assert len(ours) == len(theirs)
    for a, b in zip(ours, theirs):
        np.testing.assert_allclose(a, b, atol=1e-6)


@pytest.mark.parametrize("name", list(REQUESTS))
def test_engine_serves_the_references_tokens(reference, params, prompts,
                                             served, name):
    """Prefill then decode through the engine's cache by layer kind: each
    served token is the one the reference's full forward puts first (to
    float32 rounding of its logit)."""
    tokens = served[0][name]
    assert len(tokens) == REQUESTS[name][1]
    with jax.default_matmul_precision("highest"):
        logits = reference.served_logits(
            reference.init_on_device(CFG, SEED), prompts[name] + tokens,
            len(prompts[name]), CFG, pad_to=8)
    got = jnp.take_along_axis(logits, jnp.asarray(tokens)[:, None], 1)[:, 0]
    assert float(jnp.max(jnp.max(logits, axis=-1) - got)) <= TOL


def _program_logits(params, prompt, tokens, slot=1, slots=3, config=CONFIG):
    """Logits at the served positions by the program: prefill into `slot`
    of a cache, then one decode step a token, the other slots idle."""
    with jax.default_matmul_precision("highest"):
        first, pc = jax.jit(lambda t: sala.prefill(
            params, t, config, BUDGET))(jnp.asarray([prompt], jnp.int32))
        cache = sala.empty_cache(config, slots, BUDGET)
        cache = {k: jax.lax.dynamic_update_slice_in_dim(
            cache[k], pc[k].astype(cache[k].dtype), slot, axis=1)
            for k in cache}
        step = jax.jit(lambda c, t, p: sala.decode_step(params, config, c,
                                                        t, p))
        rows = [first[0]]
        for i, tok in enumerate(tokens[:-1]):
            t = jnp.zeros((slots,), jnp.int32).at[slot].set(tok)
            p = jnp.zeros((slots,), jnp.int32).at[slot].set(len(prompt) + i)
            logits, cache = step(cache, t, p)
            rows.append(logits[slot])
        return jnp.stack(rows), cache


@pytest.mark.parametrize("name", list(REQUESTS))
def test_logits_agree_with_the_reference_to_float32_rounding(
        reference, params, prompts, served, name):
    tokens = served[0][name]
    ours, _ = _program_logits(params, prompts[name], tokens)
    with jax.default_matmul_precision("highest"):
        theirs = reference.served_logits(
            reference.init_on_device(CFG, SEED), prompts[name] + tokens,
            len(prompts[name]), CFG, pad_to=8)
    assert float(jnp.max(jnp.abs(ours - theirs))) <= TOL


def _only_forced(score):
    """A wrong rule: the window and initial blocks alone."""
    return jnp.where(score >= sa.FORCED / 2, score, sa.NEG_INF)


def _worst_first(score):
    """A wrong rule: the pooled scores read upside down."""
    free = (score < sa.FORCED / 2) & (score > sa.NEG_INF / 2)
    return jnp.where(free, -score, score)


@pytest.mark.parametrize("wrong", [_only_forced, _worst_first],
                         ids=["window-blocks-only", "top-k-of-a-wrong-pool"])
def test_a_wrong_selection_rule_fails_the_same_comparison(
        reference, params, prompts, served, monkeypatch, wrong):
    right = sa.block_scores
    monkeypatch.setattr(sa, "block_scores",
                        lambda *a, **kw: wrong(right(*a, **kw)))
    tokens = served[0]["far-above"]
    ours, _ = _program_logits(params, prompts["far-above"], tokens)
    with jax.default_matmul_precision("highest"):
        theirs = reference.served_logits(
            reference.init_on_device(CFG, SEED),
            prompts["far-above"] + tokens, 200, CFG, pad_to=8)
    assert float(jnp.max(jnp.abs(ours - theirs))) > 100 * TOL


def test_compressed_keys_after_decode_are_those_of_the_rows(params, prompts,
                                                            served):
    """The compressed-key cache a slot has after N decode steps is the one
    recomputed from its K rows; the other slots' leaves are bit-equal to
    what they were."""
    prompt, tokens = prompts["above"], served[0]["above"]
    _, cache = _program_logits(params, prompt, tokens, slot=1)
    n = len(prompt) + len(tokens) - 1
    again = sa.compress_keys(cache["k"][:, 1, :, :n], CONFIG.sparse)
    assert again.shape[2] == n // 2 - 1
    np.testing.assert_allclose(cache["ck"][:, 1, :, :again.shape[2]], again,
                               atol=1e-6)
    empty = sala.empty_cache(CONFIG, 3, BUDGET)
    for name, leaf in cache.items():
        rows = BUDGET // 2 - 1 if name == "ck" else None
        for slot in (0, 2):     # idle slots decode garbage at position 0
            if name in ("k", "v", "tail", "state"):
                continue        # their row 0 / state is a scratch write
            assert bool(jnp.all(leaf[:, slot, :, :rows]
                                == empty[name][:, slot, :, :rows])), name


def _two_filled_slots(params, prompt):
    """A cache of two slots, both holding `prompt` as its prefill left it."""
    _, pc = jax.jit(lambda t: sala.prefill(params, t, CONFIG, BUDGET))(
        jnp.asarray([prompt], jnp.int32))
    cache = sala.empty_cache(CONFIG, 2, BUDGET)
    for slot in (0, 1):
        cache = {k: jax.lax.dynamic_update_slice_in_dim(
            cache[k], pc[k], slot, axis=1) for k in cache}
    return cache


def test_untouched_slots_stay_bit_equal(params, prompts):
    """A decode step changes only what it must of the slots it serves:
    every K/V row but the new one, every compressed key but a completed
    one, bit-equal before and after."""
    prompt = prompts["above"]
    with jax.default_matmul_precision("highest"):
        cache = _two_filled_slots(params, prompt)
        n = len(prompt)
        _, after = jax.jit(lambda c: sala.decode_step(
            params, CONFIG, c, jnp.asarray([7, 9], jnp.int32),
            jnp.asarray([n, n], jnp.int32)))(dict(cache))
    for name in ("k", "v"):
        keep = np.ones(BUDGET, bool)
        keep[n] = False
        assert bool(jnp.all(after[name][:, :, :, keep]
                            == cache[name][:, :, :, keep])), name
    done = (n + 1 - 4) // 2                 # the window position n completes
    keep = np.ones(BUDGET // 2, bool)
    keep[[done, -1]] = False
    assert bool(jnp.all(after["ck"][:, :, :, keep]
                        == cache["ck"][:, :, :, keep]))
    assert not bool(jnp.all(after["state"] == cache["state"]))


@pytest.mark.parametrize("path", ["jnp", "kernels"])
@pytest.mark.parametrize("parked", [0, 1])
def test_a_slot_that_does_not_ride_moves_no_state(params, prompts,
                                                  monkeypatch, path, parked):
    """Two filled slots, one of which the engine's `attend` array says
    does not ride: the rider's logits, new rows, compressed key and state
    are those of the step in which both ride, bit for bit; the other's
    lightning state is bit-equal before and after (and its logits finite:
    it attended to its own row). No `attend` is every slot riding. The
    plain bodies, and the kernels interpreted."""
    if path == "kernels":
        monkeypatch.setattr(lightning, "_INTERPRET", True)
        monkeypatch.setattr(sa, "_INTERPRET", True)
    prompt = prompts["above"]
    n, rider = len(prompt), 1 - parked
    with jax.default_matmul_precision("highest"):
        cache = _two_filled_slots(params, prompt)
        token = jnp.asarray([7, 9], jnp.int32)
        pos = jnp.asarray([n, n], jnp.int32)
        step = jax.jit(lambda c, attend: sala.decode_step(
            params, CONFIG, c, token, pos, attend))
        both, full = step(dict(cache), None)
        again, same = step(dict(cache), pos)
        attend = pos.at[parked].set(0)
        logits, after = step(dict(cache), attend)
    assert bool(jnp.all(both == again))
    assert all(bool(jnp.all(full[k] == same[k])) for k in full)
    assert bool(jnp.all(logits[rider] == both[rider]))
    for name in after:
        assert bool(jnp.all(after[name][:, rider] == full[name][:, rider])), \
            name
    assert bool(jnp.all(after["state"][:, parked]
                        == cache["state"][:, parked]))
    assert not bool(jnp.all(full["state"][:, parked]
                            == cache["state"][:, parked]))
    assert not bool(jnp.all(after["state"][:, rider]
                            == cache["state"][:, rider]))
    assert bool(jnp.all(jnp.isfinite(logits)))


@pytest.mark.parametrize("n", [5, 64, 100, 257])
def test_chunked_lightning_is_the_recurrence(n):
    """The chunked form (quadratic inside a chunk, the state between) is
    the step-by-step recurrence, outputs and final state, at lengths that
    are and are not whole chunks."""
    h, d = 4, 16
    keys = jax.random.split(jax.random.PRNGKey(n), 3)
    q, k, v = (jax.random.normal(key, (n, h * d), jnp.float32)
               for key in keys)
    slopes = jnp.asarray([0.9, 0.3, 0.05, 1e-5], jnp.float32)
    with jax.default_matmul_precision("highest"):
        o, state = lightning.lightning_chunk(q, k, v, slopes, chunk=32)
        s = jnp.zeros((1, 1, h, d, d), jnp.float32)
        outs = []
        for t in range(n):
            ot, s = lightning.lightning_step(
                jnp.zeros((1,), jnp.int32), jnp.exp(-slopes),
                q[t].reshape(1, h, d), k[t].reshape(1, h, d),
                v[t].reshape(1, h, d), s, 1.0)
            outs.append(ot.reshape(h * d))
    np.testing.assert_allclose(o, jnp.stack(outs), atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(state, s[0, 0], atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("name", ["above", "far-above"])
def test_a_served_tokens_stream_is_float32_under_bfloat16_weights(
        params, prompts, served, monkeypatch, name):
    """What `STREAM` is for: with the weights rounded to bfloat16, the
    logits of the served positions (a prompt's last row, then a decode
    step a token) lie several times nearer the float32 program's when the
    stream is float32 than when it is rounded to bfloat16 at every layer,
    whose rounding nothing damps."""
    low = sala.get_sala_config("sala_tiny", dtype=jnp.bfloat16)
    rounded = jax.tree.map(
        lambda a: a.astype(jnp.bfloat16) if a.ndim > 2 or a.shape[-1] > 64
        else a, params)
    upcast = jax.tree.map(lambda a: a.astype(jnp.float32), rounded)
    tokens = served[0][name]
    exact, _ = _program_logits(upcast, prompts[name], tokens)

    def err(stream):
        monkeypatch.setattr(sala, "STREAM", stream)
        got, _ = _program_logits(rounded, prompts[name], tokens, config=low)
        return float(jnp.sqrt(jnp.mean((got - exact) ** 2)))

    assert err(jnp.float32) < 0.5 * err(jnp.bfloat16)


def test_a_state_kept_in_bfloat16_is_half_the_bytes_and_nearly_the_logits(
        params, prompts, served):
    """`state_dtype` bfloat16: the state leaf and what an admission writes
    into it are bfloat16, the other leaves as they were, and the served
    positions' logits move by the state's rounding, not more."""
    low = sala.get_sala_config("sala_tiny", state_dtype=jnp.bfloat16)
    tokens = served[0]["above"]
    exact, whole = _program_logits(params, prompts["above"], tokens)
    got, cache = _program_logits(params, prompts["above"], tokens,
                                 config=low)
    assert cache["state"].dtype == jnp.bfloat16
    assert cache["state"].nbytes * 2 == whole["state"].nbytes
    assert all(cache[k].dtype == whole[k].dtype for k in whole
               if k != "state")
    assert 0 < float(jnp.max(jnp.abs(got - exact))) < 5e-3


def test_the_benchmark_family_knows_the_prefill_kernels_chunk():
    """benchmark/families/minicpm_sala/stages.py tells a traced admission's
    prompt length by how many calls of `tony_sparse_attn` a layer made: its
    copy of the chunk is the program's."""
    with open(os.path.join(ROOT, "benchmark", "families", "minicpm_sala",
                           "stages.py")) as f:
        text = f.read()
    assert f"SPARSE_CALL_QUERIES = {sa.PREFILL_CHUNK}\n" in text


def test_counters_say_how_sparse_the_reads_were(served):
    snap = served[1]
    assert snap["dense_path_admissions_total"] == 2     # 40 and 58 <= 64
    attended, context = (snap["sparse_blocks_attended_total"],
                         snap["sparse_context_blocks_total"])
    assert 0 < attended < context
    # every token of the 200-token prompt's answer reads 6 of 26 blocks
    assert CONFIG.sparse_read_blocks(201) == (6, 26)
    assert CONFIG.sparse_read_blocks(64) == (8, 8)


@pytest.mark.parametrize("kw,what", [
    (dict(prefix_sharing=True), "prefix_sharing"),
    (dict(role="prefill"), "roles"), (dict(role="decode"), "roles"),
    (dict(quant_cache=True), "quant_cache")])
def test_what_a_recurrent_state_cannot_do_is_refused(params, kw, what):
    with pytest.raises(ValueError, match=what):
        ContinuousBatchingEngine(params, CONFIG, n_slots=2, token_budget=64,
                                 **kw)


def test_migration_is_refused(params):
    engine = ContinuousBatchingEngine(params, CONFIG, n_slots=2,
                                      token_budget=64)
    with pytest.raises(ValueError, match="recurrent state"):
        engine.submit_migration({"prompt": [1], "max_new_tokens": 1}, {})


def test_an_order_of_layers_that_is_not_periodic_is_refused():
    with pytest.raises(ValueError, match="mixer_types"):
        sala.SalaConfig(n_layers=4, mixer_types=(
            sala.SPARSE, sala.LIGHTNING, sala.SPARSE, sala.SPARSE))


def test_a_llama_model_goes_the_way_it_went():
    """The shared entry points hand only a config with layers of several
    kinds to models/sala.py: a LlamaConfig's decode step is `window_logits`
    at W = 1, bit for bit, its cache the two K/V leaves, and its traced
    program names nothing of the new layers."""
    from tony_tpu.models.llama import get_config, llama_init
    config = get_config("tiny")
    params = llama_init(config, jax.random.PRNGKey(0))
    assert not gen.cache_by_kind(config)
    cache = gen.empty_cache(config, 2, 32)
    assert set(cache) == {"k", "v"} and cache["k"].shape == (2, 2, 2, 32, 16)
    prompt = jnp.asarray([[3, 1, 4, 1, 5]], jnp.int32)
    _, pc = gen.prefill(params, prompt, config, 32)
    cache = {k: jnp.concatenate([pc[k], pc[k]], axis=1) for k in pc}
    tok, pos = jnp.asarray([9, 2], jnp.int32), jnp.asarray([5, 5], jnp.int32)
    a, ca = gen.decode_step(params, config, dict(cache), tok, pos)
    b, cb = gen.window_logits(params, config, dict(cache), tok[:, None], pos)
    assert bool(jnp.all(a == b[:, 0]))
    assert all(bool(jnp.all(ca[k] == cb[k])) for k in ca)
    text = str(jax.make_jaxpr(lambda c: gen.decode_step(
        params, config, c, tok, pos))(cache))
    assert "lightning" not in text and "sparse" not in text
