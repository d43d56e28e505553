"""The hybrid of gated short-convolution and GQA attention layers with dense
and sparse-expert MLPs (models/lfm2.py, models/moe.py `grouped_expert_mlp`,
ops/expert_matmul.py) against the benchmark's plain reference of the family
(benchmark/families/lfm2_moe/reference.py: float32, no kernels, no cache,
every expert on every row), on the CPU at small widths: two dense conv
layers, then two periods of one attention and two conv layers with 8
experts of which a token takes 2.
"""

import importlib
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tony_tpu.models import by_kind_preset, lfm2, moe
from tony_tpu.ops import expert_matmul as em
from tony_tpu.serve.engine import ContinuousBatchingEngine

# (tony_tpu.models exports the function `generate` over the module's name)
gen = importlib.import_module("tony_tpu.models.generate")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = lfm2.get_config("lfm2_tiny")
# the same model in the source's key names, as a configuration file has it
CFG = {
    "hidden_size": 64, "intermediate_size": 128, "moe_intermediate_size": 32,
    "vocab_size": 256, "num_attention_heads": 4, "num_key_value_heads": 2,
    "num_hidden_layers": 8, "layer_types": list(CONFIG.layer_types),
    "num_dense_layers": 2, "num_experts": 8, "num_experts_per_tok": 2,
    "norm_topk_prob": True, "routed_scaling_factor": 1, "conv_L_cache": 3,
    "norm_eps": 1e-5, "rope_parameters": {"rope_theta": 1000000},
    "torch_dtype": "float32",
}
SEED, BUDGET = 11, 96
# (prompt tokens, served tokens): shorter than the conv's taps, and longer
REQUESTS = {"one": (1, 9), "two": (2, 12), "three": (3, 7), "long": (40, 14),
            "longer": (57, 10)}
TOL = 2e-5          # float32 rounding at logits of order 1


@pytest.fixture(scope="module")
def reference():
    spec = importlib.util.spec_from_file_location(
        "lfm2_reference", os.path.join(
            ROOT, "benchmark", "families", "lfm2_moe", "reference.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def params():
    with jax.default_matmul_precision("highest"):
        return lfm2.lfm2_init(CONFIG, jax.random.PRNGKey(SEED))


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(0)
    return {name: rng.integers(0, 256, p).tolist()
            for name, (p, _) in REQUESTS.items()}


@pytest.fixture(scope="module")
def served(params, prompts):
    """Every request through the engine, three slots for five requests:
    admitted at staggered steps, decoding at their own positions beside
    parked slots, the last two into recycled slots."""
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


def _reference_logits(reference, prompt, tokens, cfg=CFG):
    with jax.default_matmul_precision("highest"):
        return reference.served_logits(
            reference.init_on_device(cfg, SEED), prompt + tokens,
            len(prompt), cfg, pad_to=8)


def _program_logits(params, prompt, tokens, slot=1, slots=3, config=CONFIG):
    """Logits at the served positions by the program: prefill into `slot`
    of a cache, then one decode step a token, the other slots parked (they
    do not ride)."""
    with jax.default_matmul_precision("highest"):
        first, pc = jax.jit(lambda t: lfm2.prefill(
            params, t, config, BUDGET))(jnp.asarray([prompt], jnp.int32))
        cache = lfm2.empty_cache(config, slots, BUDGET)
        cache = {k: jax.lax.dynamic_update_slice_in_dim(
            cache[k], pc[k].astype(cache[k].dtype), slot, axis=1)
            for k in cache}
        step = jax.jit(lambda c, t, p, a: lfm2.decode_step(
            params, config, c, t, p, a))
        rows = [first[0]]
        for i, tok in enumerate(tokens[:-1]):
            t = jnp.zeros((slots,), jnp.int32).at[slot].set(tok)
            p = jnp.zeros((slots,), jnp.int32).at[slot].set(len(prompt) + i)
            logits, cache = step(cache, t, p, p)
            rows.append(logits[slot])
        return jnp.stack(rows), cache


def test_the_reference_draws_the_programs_weights(reference, params):
    theirs = reference.init_on_device(CFG, SEED)
    assert jax.tree.structure(params) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(theirs)):
        np.testing.assert_allclose(a, b, atol=1e-6)


@pytest.mark.parametrize("name", list(REQUESTS))
def test_engine_serves_the_references_tokens(reference, prompts, served,
                                             name):
    """Prefill then decode through the engine's cache by layer kind (K/V
    rows and the conv state): each served token is the one the reference's
    full forward puts first, to float32 rounding of its logit."""
    tokens = served[0][name]
    assert len(tokens) == REQUESTS[name][1]
    logits = _reference_logits(reference, prompts[name], tokens)
    got = jnp.take_along_axis(logits, jnp.asarray(tokens)[:, None], 1)[:, 0]
    assert float(jnp.max(jnp.max(logits, axis=-1) - got)) <= TOL


@pytest.mark.parametrize("name", list(REQUESTS))
def test_logits_agree_with_the_reference_to_float32_rounding(
        reference, params, prompts, served, name):
    """Every layer body (conv + dense, attention + experts, conv +
    experts), a prompt's and a decode step's, against the full forward."""
    tokens = served[0][name]
    ours, _ = _program_logits(params, prompts[name], tokens)
    theirs = _reference_logits(reference, prompts[name], tokens)
    assert float(jnp.max(jnp.abs(ours - theirs))) <= TOL


@pytest.mark.parametrize("n", [1, 2, 3, 4, 9])
def test_conv_state_after_a_prompt_is_its_last_gated_inputs(params, n):
    """The state a prompt of n tokens leaves is the conv's last three gated
    inputs, oldest first, zeros where the prompt had not begun; a decode
    step shifts it by one."""
    prompt = list(range(7, 7 + n))
    with jax.default_matmul_precision("highest"):
        _, cache = lfm2.prefill(params, jnp.asarray([prompt], jnp.int32),
                                CONFIG, BUDGET)
        _, longer = lfm2.prefill(
            params, jnp.asarray([prompt + [99]], jnp.int32), CONFIG, BUDGET)
        _, after = lfm2.decode_step(
            params, CONFIG, cache, jnp.asarray([99], jnp.int32),
            jnp.asarray([n], jnp.int32))
    state = cache["conv"]
    assert state.shape == (CONFIG.n_conv_layers, 1, 3, CONFIG.dim)
    assert bool(jnp.all(state[:, :, :max(3 - n, 0)] == 0))
    assert bool(jnp.all(jnp.any(state[:, :, max(3 - n, 0):] != 0, axis=-1)))
    np.testing.assert_allclose(after["conv"], longer["conv"], atol=1e-5)
    np.testing.assert_allclose(after["conv"][:, :, :2], state[:, :, 1:],
                               atol=0)


def _dense_sum(u, layer, experts, spec, wrong=None):
    """The expert MLP the dense way, in plain jnp: every expert on every
    row, weighted by the row's gate."""
    z = u @ experts["router"][layer]
    s = jax.nn.sigmoid(z)
    pick = s + experts["expert_bias"][layer]
    chosen = jnp.argsort(-pick, axis=-1, stable=True)[:, :spec.top_k]
    w = jnp.take_along_axis(s, chosen, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6)
    gate = jnp.zeros_like(s).at[jnp.arange(u.shape[0])[:, None],
                                chosen].set(w)
    h = jax.nn.silu(jnp.einsum("td,edf->tef", u, experts["w1"][layer])) \
        * jnp.einsum("td,edf->tef", u, experts["w3"][layer])
    return jnp.einsum("te,ted->td", gate,
                      jnp.einsum("tef,efd->ted", h, experts["w2"][layer]))


def _experts(key, layers=2, e=8, d=64, f=32):
    ks = jax.random.split(key, 4)
    return {"router": jax.random.normal(ks[0], (layers, d, e)) * d ** -0.5,
            "expert_bias": jnp.zeros((layers, e)),
            "w1": jax.random.normal(ks[1], (layers, e, d, f)) * d ** -0.5,
            "w3": jax.random.normal(ks[2], (layers, e, d, f)) * d ** -0.5,
            "w2": jax.random.normal(ks[3], (layers, e, f, d)) * f ** -0.5}


@pytest.mark.parametrize("skew", ["as-drawn", "all-on-one-expert"])
def test_routing_drops_no_token_and_equals_the_dense_sum(skew):
    """At any imbalance: with a bias that sends every token's first choice
    to expert 5, that expert gets all 50 rows (a capacity of 1.25 would
    have kept 15) and the result is still the dense sum."""
    spec = moe.RouterSpec(n_experts=8, top_k=2)
    experts = _experts(jax.random.PRNGKey(3))
    if skew == "all-on-one-expert":
        experts["expert_bias"] = experts["expert_bias"].at[:, 5].set(10.0)
    u = jax.random.normal(jax.random.PRNGKey(4), (50, 64))
    with jax.default_matmul_precision("highest"):
        out, counts = moe.grouped_expert_mlp(u, jnp.int32(1), experts, spec)
        want = _dense_sum(u, 1, experts, spec)
    assert int(counts.sum()) == 100
    if skew == "all-on-one-expert":
        assert int(counts[5]) == 50
    np.testing.assert_allclose(out, want, atol=2e-5)


@pytest.mark.parametrize("tile_rows,sizes", [
    (16, [0, 3, 0, 0, 17, 1, 0, 16]), (16, [0] * 8), (32, [40, 0, 0, 0, 0, 0,
                                                            0, 5])],
    ids=["empty-groups-between", "no-rows-at-all", "two-tiles-of-one"])
@pytest.mark.parametrize("gated", [False, True], ids=["one-leaf", "gated"])
def test_grouped_matmul_kernel_equals_jnp_with_empty_groups(
        tile_rows, sizes, gated):
    """`tony_expert_matmul` in interpret mode against a plain loop, groups
    of 0 rows among the others: the rows of a used tile are their expert's
    product, whatever lies in the tiles nobody used."""
    e, k, n, layers = len(sizes), 64, 128, 3
    rows = em.padded_rows(sum(sizes), e, tile_rows)
    tiles = [-(-s // tile_rows) for s in sizes]
    tile_expert = np.repeat(np.arange(e), tiles)
    used = len(tile_expert)
    tile_expert = np.concatenate(
        [tile_expert, np.full(rows // tile_rows - used, e - 1)])
    keys = jax.random.split(jax.random.PRNGKey(sum(sizes)), 3)
    x = jax.random.normal(keys[0], (rows, k))
    ws = [jax.random.normal(key, (layers, e, k, n)) * k ** -0.5
          for key in keys[1:3]][:2 if gated else 1]
    args = (jnp.asarray([2], jnp.int32),
            jnp.asarray(tile_expert, jnp.int32),
            jnp.asarray([used], jnp.int32), x, *ws)
    with jax.default_matmul_precision("highest"):
        got = em._matmul_pallas(*args, tile_rows=tile_rows,
                                out_dtype=jnp.float32, interpret=True)
        plain = em._matmul_jnp(*args, tile_rows=tile_rows,
                               out_dtype=jnp.float32)
        want = []
        for t in range(used):
            xt = x[t * tile_rows:(t + 1) * tile_rows]
            y = xt @ ws[0][2, tile_expert[t]]
            if gated:
                y = jax.nn.silu(y) * (xt @ ws[1][2, tile_expert[t]])
            want.append(y)
    live = used * tile_rows
    if used:
        np.testing.assert_allclose(got[:live], jnp.concatenate(want),
                                   atol=2e-5)
        np.testing.assert_allclose(plain[:live], jnp.concatenate(want),
                                   atol=2e-5)
    assert bool(jnp.all(plain[live:] == 0))


def test_tile_rows_follow_the_mean_group():
    """16 rows for a decode step, the mean group for an admission, at most
    256; the layout holds every group's slack."""
    assert em.tile_rows_for(64 * 4, 64) == 16
    assert [em.tile_rows_for(t * 4, 64) for t in (256, 512, 1024, 2048,
                                                  4096, 8192)] \
        == [16, 32, 64, 128, 256, 256]
    assert em.padded_rows(256, 64, 16) == 256 + 64 * 15
    assert em.padded_rows(16384, 64, 256) % 256 == 0


def test_a_slot_that_does_not_ride_reaches_no_expert(params, prompts):
    """Two slots hold the same stream and a third is parked: with the
    third riding too the step counts more rows and hits at least as many
    experts; parked (`attend` 0) it adds no row to any expert and leaves
    `experts_hit` where the two riders alone put it, and the riders'
    logits are the same either way."""
    prompt = prompts["long"]
    with jax.default_matmul_precision("highest"):
        _, pc = lfm2.prefill(params, jnp.asarray([prompt], jnp.int32),
                             CONFIG, BUDGET)
        cache = lfm2.empty_cache(CONFIG, 3, BUDGET)
        for slot in (0, 1):
            cache = {k: jax.lax.dynamic_update_slice_in_dim(
                cache[k], pc[k], slot, axis=1) for k in cache}
        n = len(prompt)
        tok = jnp.asarray([7, 7, 123], jnp.int32)
        pos = jnp.asarray([n, n, BUDGET - 1], jnp.int32)
        step = jax.jit(lambda a: lfm2.decode_step_counted(
            params, CONFIG, dict(cache), tok, pos, a))
        parked, _, few = step(jnp.asarray([n, n, 0], jnp.int32))
        riding, _, more = step(pos)
        alone, _, one = step(jnp.asarray([n, 0, 0], jnp.int32))
    layers, k = CONFIG.n_expert_layers, CONFIG.top_k
    assert int(few[1]) == 2 * k * layers and int(more[1]) == 3 * k * layers
    # the two riders hold one stream: they hit what one of them hits
    assert int(few[0]) == int(one[0]) == k * layers
    assert int(more[0]) > int(few[0])
    np.testing.assert_allclose(parked[:2], riding[:2], atol=1e-5)


def test_counters_are_read_back_from_the_device(served):
    """`moe_*_total` on the engine's snapshot: the expert layers of every
    step read, the rows they served (top-2 of each rider's token, the
    parked slots none) and the experts hit, which the step itself counted:
    between one and `rows` an expert layer, at most all 8."""
    snap = served[1]
    layers = CONFIG.n_expert_layers
    assert snap["moe_layer_steps_total"] == layers * snap[
        "decode_steps_total"]
    assert snap["moe_rows_total"] == layers * CONFIG.top_k * (
        snap["decode_slot_steps_total"]
        + snap["decode_slot_steps_discarded_total"])
    hit = snap["moe_experts_hit_total"]
    assert snap["moe_layer_steps_total"] <= hit <= min(
        snap["moe_rows_total"], 8 * snap["moe_layer_steps_total"])
    # this model's attention reads by the engine's attend lengths
    assert 0 < snap["cache_rows_read_total"] < snap[
        "cache_rows_budget_total"]


def _softmax_scores(u, router, bias, spec):
    z = jnp.dot(u, router)
    scores = jax.nn.softmax(z, axis=-1)
    _, chosen = jax.lax.top_k(scores + bias, spec.top_k)
    w = jnp.take_along_axis(scores, chosen, axis=-1)
    return chosen.astype(jnp.int32), w / (w.sum(-1, keepdims=True) + 1e-6)


def _weights_with_the_bias(u, router, bias, spec):
    scores = jax.nn.sigmoid(jnp.dot(u, router)) + bias + 0.25
    _, chosen = jax.lax.top_k(scores, spec.top_k)
    w = jnp.take_along_axis(scores, chosen, axis=-1)
    return chosen.astype(jnp.int32), w / (w.sum(-1, keepdims=True) + 1e-6)


@pytest.mark.parametrize("wrong", [_softmax_scores, _weights_with_the_bias],
                         ids=["softmax-for-sigmoid", "weights-with-the-bias"])
def test_a_wrong_routing_rule_fails_the_same_comparison(
        reference, params, prompts, served, monkeypatch, wrong):
    monkeypatch.setattr(moe, "route_topk", wrong)
    tokens = served[0]["long"]
    ours, _ = _program_logits(params, prompts["long"], tokens)
    theirs = _reference_logits(reference, prompts["long"], tokens)
    assert float(jnp.max(jnp.abs(ours - theirs))) > 100 * TOL


def test_altered_tokens_fail_the_served_comparison(reference, prompts,
                                                   served):
    """What `--sabotage flip` does to a replica (every sampled token + 1):
    the reference's best logit then lies far above the served token's."""
    tokens = [(t + 1) % 256 for t in served[0]["long"]]
    logits = _reference_logits(reference, prompts["long"], tokens)
    got = jnp.take_along_axis(logits, jnp.asarray(tokens)[:, None], 1)[:, 0]
    assert float(jnp.max(jnp.max(logits, axis=-1) - got)) > 1000 * TOL


def test_the_int8_control_is_another_computation(reference, prompts, served):
    """The serving control: the reference with every matmul operand rounded
    to int8 gives other logits than itself in float32, by far more than
    the program does."""
    tokens = served[0]["long"]
    plain = _reference_logits(reference, prompts["long"], tokens)
    with jax.default_matmul_precision("highest"):
        low = reference.served_logits(
            reference.init_on_device(CFG, SEED), prompts["long"] + tokens,
            len(prompts["long"]), CFG, pad_to=8, precision="int8")
    assert low.shape == plain.shape
    assert float(jnp.max(jnp.abs(low - plain))) > 100 * TOL


def test_bfloat16_weights_meet_the_stream_as_two_halves(params, prompts,
                                                        monkeypatch):
    """What `split_rows` is for: with the weights rounded to bfloat16 the
    logits of the served positions lie an order of magnitude nearer the
    float32 program's when the stream's rows go into each matmul as two
    bfloat16 halves than when they are rounded to one, whose error picks
    other experts."""
    low = lfm2.get_config("lfm2_tiny", dtype=jnp.bfloat16)
    rounded = jax.tree.map(
        lambda a: a.astype(jnp.bfloat16) if a.ndim > 2 or a.shape[-1] > 64
        else a, params)
    upcast = jax.tree.map(lambda a: a.astype(jnp.float32), rounded)
    tokens = list(range(3, 15))
    exact, _ = _program_logits(upcast, prompts["long"], tokens)

    def err():
        got, cache = _program_logits(rounded, prompts["long"], tokens,
                                     config=low)
        assert cache["conv"].dtype == jnp.float32
        assert cache["k"].dtype == jnp.bfloat16
        return float(jnp.sqrt(jnp.mean((got - exact) ** 2)))

    halves = err()
    one = lambda x, dtype: x.astype(dtype)      # noqa: E731
    monkeypatch.setattr(em, "split_rows", one)
    monkeypatch.setattr(lfm2, "split_rows", one)
    assert halves < 0.1 * err()


def test_int8_cache_and_what_a_conv_state_cannot_do(params, prompts):
    """The K/V rows take the program's int8 form (the benchmark's bytes
    control); prefix pages and K/V migration are refused, since a slot's
    cache is not its K/V rows alone."""
    engine = ContinuousBatchingEngine(params, CONFIG, n_slots=2,
                                      token_budget=BUDGET, quant_cache=True)
    assert engine._cache["k"].dtype == jnp.int8
    assert engine._cache["conv"].dtype == jnp.float32
    plain = ContinuousBatchingEngine(params, CONFIG, n_slots=2,
                                     token_budget=BUDGET)
    got = {}
    for name, eng in (("int8", engine), ("plain", plain)):
        handle = eng.submit(prompts["long"], 6)
        while eng.step():
            pass
        got[name] = handle.result(timeout=1)
    assert len(got["int8"]) == 6 and got["int8"][0] == got["plain"][0]
    for kw in (dict(prefix_sharing=True), dict(role="prefill")):
        with pytest.raises(ValueError, match="recurrent state"):
            ContinuousBatchingEngine(params, CONFIG, n_slots=2,
                                     token_budget=BUDGET, **kw)


def test_an_order_of_layers_that_is_not_periodic_is_refused():
    with pytest.raises(ValueError, match="layer_types"):
        lfm2.Lfm2Config(n_layers=6, layer_types=(
            "conv", "conv", "full_attention", "conv", "full_attention",
            "full_attention"))
    with pytest.raises(ValueError, match="layer_types"):
        lfm2.Lfm2Config(n_layers=4, n_dense_layers=2, layer_types=(
            "conv", "full_attention", "full_attention", "conv"))


def test_a_preset_names_its_own_module():
    """The seam: `python -m tony_tpu.serve --config <preset>` and
    models/generate.py find a model of several layer kinds through the
    module its config names, and name none themselves."""
    from tony_tpu.models import sala
    assert by_kind_preset("lfm2_tiny") is lfm2
    assert by_kind_preset("sala_tiny") is sala
    assert by_kind_preset("tiny") is None
    assert gen.kind_module(CONFIG) is lfm2
    assert gen.kind_module(sala.get_config("sala_tiny")) is sala
    for name in ("models/generate.py", "serve/__main__.py"):
        with open(os.path.join(ROOT, "tony_tpu", name)) as f:
            text = f.read()
        assert "sala" not in text and "lfm2" not in text, name
