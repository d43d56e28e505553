"""Model + training tests on the virtual CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tony_tpu.models.llama import (
    get_config, llama_forward, llama_init, llama_loss, llama_param_axes,
)
from tony_tpu.models.mnist import mnist_accuracy, mnist_init, mnist_loss
from tony_tpu.models.linear import linreg_init, linreg_loss
from tony_tpu.parallel import make_mesh, plan_mesh, shard_pytree
from tony_tpu.train.checkpoint import (
    latest_step, restore_checkpoint, save_checkpoint,
)
from tony_tpu.train.data import (
    synthetic_linreg, synthetic_mnist, synthetic_tokens,
)
from tony_tpu.train.step import make_train_step
from tony_tpu.train.trainer import Trainer, TrainerConfig


def test_llama_forward_shapes_and_param_count():
    cfg = get_config("tiny")
    params = llama_init(cfg, jax.random.PRNGKey(0))
    tokens = jnp.zeros((2, 16), jnp.int32)
    logits = llama_forward(params, tokens, cfg)
    assert logits.shape == (2, 16, cfg.vocab_size)
    counted = sum(x.size for x in jax.tree.leaves(params))
    assert counted == cfg.num_params()
    # axes tree matches params tree structure
    axes = llama_param_axes(cfg)
    jax.tree.map(lambda p, a: None, params, axes,
                 is_leaf=lambda x: isinstance(x, tuple))


def test_llama_causality():
    """Future tokens must not affect past logits."""
    cfg = get_config("tiny")
    params = llama_init(cfg, jax.random.PRNGKey(0))
    t1 = jnp.array([[1, 2, 3, 4, 5, 6, 7, 8]], jnp.int32)
    t2 = t1.at[0, -1].set(99)  # change only the last token
    l1 = llama_forward(params, t1, cfg)
    l2 = llama_forward(params, t2, cfg)
    np.testing.assert_allclose(l1[0, :-1], l2[0, :-1], atol=1e-5)
    assert not np.allclose(l1[0, -1], l2[0, -1])


def test_llama_trains_on_mesh():
    """Loss must descend under a dp+fsdp+tp mesh with sharded params."""
    cfg = get_config("tiny")
    mesh = make_mesh(plan_mesh(8, tp=2))
    params = llama_init(cfg, jax.random.PRNGKey(0))
    params = shard_pytree(params, llama_param_axes(cfg), mesh)
    opt = optax.adam(1e-2)
    step = make_train_step(lambda p, b: llama_loss(p, b, cfg), opt)
    data = synthetic_tokens(8, 32, cfg.vocab_size)
    with jax.set_mesh(mesh):
        opt_state = jax.device_put(opt.init(params))
        losses = []
        for _ in range(30):
            batch = {k: jax.device_put(v) for k, v in next(data).items()}
            params, opt_state, loss = step(params, opt_state, batch)
            losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.8, losses[::10]


def test_llama_trains_with_sequence_parallelism():
    """sp=2 ring-attention path: loss finite and decreasing."""
    cfg = get_config("tiny")
    mesh = make_mesh(plan_mesh(8, sp=2, tp=2, dp=2, fsdp=1))
    params = llama_init(cfg, jax.random.PRNGKey(0))
    params = shard_pytree(params, llama_param_axes(cfg), mesh)
    opt = optax.adam(1e-2)
    step = make_train_step(lambda p, b: llama_loss(p, b, cfg), opt)
    data = synthetic_tokens(4, 32, cfg.vocab_size)
    with jax.set_mesh(mesh):
        opt_state = jax.device_put(opt.init(params))
        losses = []
        for _ in range(10):
            batch = {k: jax.device_put(v) for k, v in next(data).items()}
            params, opt_state, loss = step(params, opt_state, batch)
            losses.append(float(loss))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]


def test_sp_matches_no_sp_forward():
    """The ring-attention path must compute the same function."""
    cfg = get_config("tiny")
    params = llama_init(cfg, jax.random.PRNGKey(0))
    tokens = jnp.arange(64, dtype=jnp.int32).reshape(2, 32) % cfg.vocab_size
    plain = llama_forward(params, tokens, cfg)
    mesh = make_mesh(plan_mesh(8, sp=4, dp=2, fsdp=1))
    with jax.set_mesh(mesh):
        sp = jax.jit(lambda p, t: llama_forward(p, t, cfg))(params, tokens)
    np.testing.assert_allclose(np.asarray(plain), np.asarray(sp),
                               atol=2e-4, rtol=2e-4)


def test_mnist_learns():
    params = mnist_init(jax.random.PRNGKey(0))
    opt = optax.adam(1e-3)
    step = make_train_step(mnist_loss, opt)
    opt_state = opt.init(params)
    data = synthetic_mnist(64)
    for _ in range(60):
        params, opt_state, loss = step(params, opt_state, next(data))
    acc = float(mnist_accuracy(params, next(data)))
    assert acc > 0.9, acc


def test_linreg_learns():
    params = linreg_init(jax.random.PRNGKey(0))
    opt = optax.sgd(0.1)
    step = make_train_step(linreg_loss, opt)
    opt_state = opt.init(params)
    data = synthetic_linreg(64)
    for _ in range(100):
        params, opt_state, loss = step(params, opt_state, next(data))
    assert float(loss) < 0.01


def test_checkpoint_roundtrip(tmp_path):
    state = {"params": {"w": jnp.arange(6.0).reshape(2, 3)},
             "step": jnp.asarray(7)}
    save_checkpoint(str(tmp_path), 7, state)
    save_checkpoint(str(tmp_path), 3, state)
    assert latest_step(str(tmp_path)) == 7
    restored = restore_checkpoint(str(tmp_path))
    np.testing.assert_array_equal(restored["params"]["w"],
                                  np.arange(6.0).reshape(2, 3))
    assert int(restored["step"]) == 7


def test_trainer_resume(tmp_path):
    """Trainer must resume from the latest checkpoint (AM-retry survival)."""
    cfg = TrainerConfig(num_steps=5, log_every=1, checkpoint_every=5,
                        checkpoint_dir=str(tmp_path), learning_rate=1e-2,
                        warmup_steps=1)
    data = synthetic_mnist(32)
    t1 = Trainer(mnist_loss, mnist_init, data, cfg)
    t1.run()
    assert latest_step(str(tmp_path)) == 5
    cfg2 = TrainerConfig(num_steps=10, log_every=1, checkpoint_every=5,
                         checkpoint_dir=str(tmp_path), learning_rate=1e-2,
                         warmup_steps=1)
    t2 = Trainer(mnist_loss, mnist_init, data, cfg2)
    t2.setup()
    assert t2.step == 5  # resumed, not restarted
    t2.run()
    assert latest_step(str(tmp_path)) == 10


def test_llama_ulysses_sp_mode_trains():
    """Full llama step with ulysses SP on a seq-sharded mesh."""
    from functools import partial
    import optax
    from tony_tpu.models.llama import (
        get_config, llama_init, llama_loss, llama_param_axes,
    )
    from tony_tpu.parallel import make_mesh, plan_mesh, shard_pytree
    from tony_tpu.train.step import make_train_step

    mesh = make_mesh(plan_mesh(8, sp=2, tp=2))
    config = get_config("tiny", sp_mode="ulysses")
    params = shard_pytree(llama_init(config, jax.random.PRNGKey(0)),
                          llama_param_axes(config), mesh)
    opt = optax.adam(1e-3)
    step = make_train_step(partial(llama_loss, config=config), opt)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 65), 0,
                                config.vocab_size, jnp.int32)
    with jax.set_mesh(mesh):
        opt_state = jax.jit(opt.init)(params)
        _, _, loss = step(params, opt_state, {"tokens": tokens})
    assert np.isfinite(float(loss))


def test_grad_accum_matches_full_batch():
    """grad_accum=2 on the same global batch must produce the SAME update
    as a single full-batch step. SGD, not adam: the update is then linear
    in the mean gradient, so this pins the accumulation math itself
    (adam's first step is ~sign(g), which amplifies f32 accumulation-order
    noise wherever g is near zero)."""
    cfg = get_config("tiny")
    params = llama_init(cfg, jax.random.PRNGKey(0))
    opt = optax.sgd(0.1)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 33), 0,
                                cfg.vocab_size, jnp.int32)
    batch = {"tokens": tokens}
    loss_fn = lambda p, b: llama_loss(p, b, cfg)  # noqa: E731

    step_full = make_train_step(loss_fn, opt)
    step_accum = make_train_step(loss_fn, opt, grad_accum=2)
    import copy
    p1, o1, l1 = step_full(copy.deepcopy(params), opt.init(params), batch)
    p2, o2, l2 = step_accum(copy.deepcopy(params), opt.init(params), batch)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=1e-6)


def test_grad_accum_on_mesh():
    """grad_accum under a dp+fsdp+tp mesh: loss decreases, shapes hold."""
    cfg = get_config("tiny")
    mesh = make_mesh(plan_mesh(8, tp=2))
    params = shard_pytree(llama_init(cfg, jax.random.PRNGKey(0)),
                          llama_param_axes(cfg), mesh)
    opt = optax.adam(1e-2)
    step = make_train_step(lambda p, b: llama_loss(p, b, cfg), opt,
                           grad_accum=2)
    data = synthetic_tokens(8, 32, cfg.vocab_size)
    with jax.set_mesh(mesh):
        opt_state = jax.jit(opt.init)(params)
        losses = []
        for _ in range(10):
            batch = {k: jax.device_put(v) for k, v in next(data).items()}
            params, opt_state, loss = step(params, opt_state, batch)
            losses.append(float(loss))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]


def test_grad_accum_rejects_indivisible_batch():
    cfg = get_config("tiny")
    params = llama_init(cfg, jax.random.PRNGKey(0))
    opt = optax.adam(1e-2)
    step = make_train_step(lambda p, b: llama_loss(p, b, cfg), opt,
                           grad_accum=3, jit=False)
    tokens = jnp.zeros((4, 33), jnp.int32)
    import pytest
    with pytest.raises(ValueError, match="not divisible"):
        step(params, opt.init(params), {"tokens": tokens})


def test_trainer_eval_loop():
    """eval_every runs the held-out loss on cadence; eval loss tracks the
    train loss down on the same synthetic distribution."""
    cfg = TrainerConfig(num_steps=6, log_every=2, eval_every=3,
                        eval_batches=2, learning_rate=1e-2, warmup_steps=1)
    t = Trainer(mnist_loss, mnist_init, synthetic_mnist(32), cfg,
                eval_data_iter=synthetic_mnist(32, seed=9))
    t.run()
    evals = [m for m in t.metrics_history if "eval_loss" in m]
    assert [m["step"] for m in evals] == [3, 6]
    assert t.last_eval_loss is not None
    assert np.isfinite(t.last_eval_loss)


def test_resnet_learns():
    """Conv family (models/resnet.py): loss descends on synthetic mnist."""
    from tony_tpu.models.resnet import (
        get_resnet_config, resnet_accuracy, resnet_init, resnet_loss,
    )

    cfg = get_resnet_config("resnet_tiny")
    params = resnet_init(cfg, jax.random.PRNGKey(0))
    opt = optax.adam(3e-3)
    step = make_train_step(lambda p, b: resnet_loss(p, b, cfg), opt)
    opt_state = jax.jit(opt.init)(params)
    data = synthetic_mnist(32)
    losses = []
    for _ in range(30):
        params, opt_state, loss = step(params, opt_state, next(data))
        losses.append(float(loss))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0] * 0.5, losses[::10]
    acc = float(resnet_accuracy(params, next(data), cfg))
    assert acc > 0.5, acc


def test_resnet50_proxy_shapes():
    """The 50-layer-equivalent preset compiles and produces class logits."""
    from tony_tpu.models.resnet import (
        get_resnet_config, resnet_forward, resnet_init,
    )

    cfg = get_resnet_config("resnet50_proxy", num_classes=12,
                            stages=((1, 8, 1), (1, 16, 2)), stem_channels=8,
                            groups=4)
    params = resnet_init(cfg, jax.random.PRNGKey(0))
    imgs = jnp.zeros((2, 32, 32, 3), jnp.float32)
    logits = resnet_forward(params, imgs, cfg)
    assert logits.shape == (2, 12)
    assert logits.dtype == jnp.float32


def test_f32_master_rescues_bf16_underflow():
    """With lr small enough that bf16 updates underflow the ULP, plain
    bf16 adam stalls EXACTLY (params unchanged) while the f32-master
    wrapper keeps making progress — the defining property of master
    weights."""
    from tony_tpu.train.precision import with_f32_master

    w0_host = np.full((64,), 1.0, np.float32)  # ULP(1.0) = 2^-8 in bf16

    def fresh():
        return {"w": jnp.full((64,), 1.0, jnp.bfloat16)}

    def loss_fn(params, batch):
        return jnp.sum((params["w"].astype(jnp.float32) - 2.0) ** 2)

    # sgd step = lr * grad = 1e-5 * 2 ≈ 2e-5 << 2^-8: underflows in bf16
    plain = optax.sgd(1e-5)
    step_plain = make_train_step(loss_fn, plain)
    p1, s1 = fresh(), plain.init(fresh())
    for _ in range(50):
        p1, s1, _ = step_plain(p1, s1, None)
    np.testing.assert_array_equal(np.asarray(p1["w"], np.float32),
                                  w0_host)  # stalled exactly

    master = with_f32_master(optax.sgd(1e-5))
    step_m = make_train_step(loss_fn, master)
    p2, s2 = fresh(), master.init(fresh())
    for _ in range(300):
        p2, s2, _ = step_m(p2, s2, None)
    # loss pulls w from 1.0 toward 2.0: the master accumulated
    # ~300*2e-5 = 6e-3 of progress, and 6e-3 > ULP(1.0)=2^-8 so the
    # visible bf16 params moved too
    assert float(np.asarray(s2["master"]["w"], np.float32)[0]) > 1.004
    assert float(np.asarray(p2["w"], np.float32)[0]) > 1.0


def test_f32_master_trains_llama_bf16_on_mesh():
    """Full sharded step with master weights on the bf16 tiny config."""
    cfg = get_config("tiny", dtype=jnp.bfloat16)
    from tony_tpu.train.precision import with_f32_master

    mesh = make_mesh(plan_mesh(8, tp=2))
    params = shard_pytree(llama_init(cfg, jax.random.PRNGKey(0)),
                          llama_param_axes(cfg), mesh)
    opt = with_f32_master(optax.adam(1e-2))
    step = make_train_step(lambda p, b: llama_loss(p, b, cfg), opt)
    data = synthetic_tokens(8, 32, cfg.vocab_size)
    with jax.set_mesh(mesh):
        opt_state = jax.jit(opt.init)(params)
        losses = []
        for _ in range(10):
            batch = {k: jax.device_put(v) for k, v in next(data).items()}
            params, opt_state, loss = step(params, opt_state, batch)
            losses.append(float(loss))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]
    # params stayed bf16; master is f32
    assert params["embed"].dtype == jnp.bfloat16
    assert opt_state["master"]["embed"].dtype == jnp.float32


def test_master_weights_with_grad_accum_keeps_f32_grads():
    """grad_accum + master weights together: the f32-accumulated mean
    gradient must reach the master un-quantized (params stay bf16, loss
    finite, master f32) — the combination the trainer wires."""
    from tony_tpu.train.precision import with_f32_master

    cfg = get_config("tiny", dtype=jnp.bfloat16)
    params = llama_init(cfg, jax.random.PRNGKey(0))
    opt = with_f32_master(optax.adam(1e-2))
    step = make_train_step(lambda p, b: llama_loss(p, b, cfg), opt,
                           grad_accum=2, emit_accum_dtype=True)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 33), 0,
                                cfg.vocab_size, jnp.int32)
    opt_state = jax.jit(opt.init)(params)
    for _ in range(3):
        params, opt_state, loss = step(params, opt_state,
                                       {"tokens": tokens})
    assert np.isfinite(float(loss))
    assert params["embed"].dtype == jnp.bfloat16
    assert opt_state["master"]["embed"].dtype == jnp.float32


def test_vit_learns():
    """ViT family (models/vit.py): attention-on-images loss descends on a
    separable synthetic task."""
    from tony_tpu.models.vit import get_config, vit_init, vit_loss

    cfg = get_config("vit_tiny", image_size=16, patch_size=4,
                     in_channels=1, n_layers=2)
    params = vit_init(cfg, jax.random.PRNGKey(0))
    opt = optax.adam(3e-3)
    step = make_train_step(lambda p, b: vit_loss(p, b, cfg), opt)
    opt_state = jax.jit(opt.init)(params)
    rng = np.random.default_rng(0)
    labels = rng.integers(0, cfg.num_classes, 64).astype(np.int32)
    # class-dependent mean intensity: linearly separable from patches
    images = (rng.normal(0, 0.1, (64, 16, 16, 1))
              + labels[:, None, None, None] / 10.0).astype(np.float32)
    batch = {"images": jnp.asarray(images), "labels": jnp.asarray(labels)}
    losses = []
    for _ in range(40):
        params, opt_state, loss = step(params, opt_state, batch)
        losses.append(float(loss))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0] * 0.5, losses[::10]


def test_vit_s16_proxy_shapes():
    from tony_tpu.models.vit import get_config, vit_forward, vit_init

    cfg = get_config("vit_s16_proxy", image_size=32, n_layers=2,
                     num_classes=7)
    params = vit_init(cfg, jax.random.PRNGKey(0))
    logits = vit_forward(params, jnp.zeros((2, 32, 32, 3)), cfg)
    assert logits.shape == (2, 7) and logits.dtype == jnp.float32


def test_vit_trains_sharded_on_mesh():
    """Sharded ViT train step on the fsdp x tp mesh: non-causal flash
    dispatch under a multi-axis mesh, params sharded by vit_param_axes."""
    from tony_tpu.models.vit import (
        get_config, vit_init, vit_loss, vit_param_axes,
    )
    from tony_tpu.parallel import make_mesh, plan_mesh
    from tony_tpu.parallel.sharding import shard_pytree

    cfg = get_config("vit_tiny", image_size=16, patch_size=4,
                     in_channels=1)
    mesh = make_mesh(plan_mesh(8, tp=2))
    params = vit_init(cfg, jax.random.PRNGKey(0))
    want = float(vit_loss(params, {
        "images": jnp.ones((8, 16, 16, 1)),
        "labels": jnp.zeros((8,), jnp.int32)}, cfg))
    params = shard_pytree(params, vit_param_axes(cfg), mesh)
    opt = optax.adam(1e-3)
    step = make_train_step(lambda p, b: vit_loss(p, b, cfg), opt)
    with jax.set_mesh(mesh):
        opt_state = jax.jit(opt.init)(params)
        batch = {"images": jnp.ones((8, 16, 16, 1)),
                 "labels": jnp.zeros((8,), jnp.int32)}
        params, opt_state, loss = step(params, opt_state, batch)
    np.testing.assert_allclose(float(loss), want, rtol=1e-4)


def test_llama3_70b_preset_geometry():
    """The 70B preset carries the Llama-3-70B geometry and ~70B params
    (the >16B pp regime docs/SCALING.md compiles against v5p-128)."""
    from tony_tpu.models.llama import get_config

    cfg = get_config("llama3_70b")
    assert (cfg.dim, cfg.n_layers, cfg.n_heads, cfg.n_kv_heads,
            cfg.ffn_dim) == (8192, 80, 64, 8, 28_672)
    assert 6.9e10 < cfg.num_params() < 7.2e10, cfg.num_params()


def test_trainer_double_setup_mesh_loss():
    """setup() twice (session retry path) must not stack a duplicate
    mesh= kwarg onto a loss_takes_mesh loss (r4 advisor)."""
    def meshy_loss(params, batch, mesh=None):
        assert mesh is not None
        return mnist_loss(params, batch)

    cfg = TrainerConfig(num_steps=2, log_every=1, warmup_steps=1)
    t = Trainer(meshy_loss, mnist_init, synthetic_mnist(32), cfg,
                loss_takes_mesh=True)
    t.setup()
    t.setup()          # retry: rebinds against the ORIGINAL loss_fn
    t.run()
    assert t.last_loss is not None


# -- the replay's rule (PERF.md §3): what a rematted block saves ------------

def _remat_model(model, **overrides):
    """(config, init, loss, block) of the dense or the MoE model at the
    tiny shapes."""
    from tony_tpu.models import llama, moe
    if model == "dense":
        return (get_config("tiny", **overrides), llama_init, llama_loss,
                llama._block)
    return (moe.get_moe_config("moe_tiny", **overrides), moe.moe_init,
            moe.moe_loss, moe._block)


@pytest.mark.parametrize("policy", ["save_flash", "full"])
@pytest.mark.parametrize("model", ["dense", "moe"])
def test_remat_replay_gives_the_unrematted_loss_and_gradients(model, policy):
    """A saved tensor is the very value the replay would recompute, so
    what a policy saves changes memory and time, never numbers: the loss
    and every gradient leaf under `remat=True` are those of `remat=False`,
    under either policy, for the dense block and the MoE block (bit for
    bit on the CPU)."""
    plain, init, loss_fn, _ = _remat_model(model, remat=False)
    remat, _, _, _ = _remat_model(model, remat=True, remat_policy=policy)
    params = init(plain, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 33), 0,
                                plain.vocab_size, jnp.int32)
    batch = {"tokens": tokens}
    want_loss, want = jax.jit(jax.value_and_grad(
        lambda p: loss_fn(p, batch, plain)))(params)
    got_loss, got = jax.jit(jax.value_and_grad(
        lambda p: loss_fn(p, batch, remat)))(params)
    assert float(got_loss) == float(want_loss)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("model", ["dense", "moe"])
def test_save_flash_saves_the_named_tensors_and_the_blocks_inputs(
        model, capsys):
    """What one rematted block keeps for its backward under `save_flash`:
    its inputs (the stream and the layer's weights), the RoPE tables, and
    exactly the tensors the policy names — the flash kernel's five
    residuals, the attention sublayer's projected output, and (dense
    block) `w_gate`'s result. Nothing else: not `w_up`'s result, not a
    norm's output, not the SwiGLU product. Under `full`, the inputs only."""
    from functools import partial

    from jax.ad_checkpoint import print_saved_residuals

    from tony_tpu.models.llama import SAVE_FLASH_NAMES, rope_tables

    b, s = 2, 16

    def saved(policy):
        config, init, _, block = _remat_model(model, remat=True,
                                              remat_policy=policy)
        layer = jax.tree.map(lambda l: l[0],
                             init(config, jax.random.PRNGKey(0))["layers"])
        cos, sin = rope_tables(config, s)
        block = jax.checkpoint(partial(block, config, cos, sin),
                               policy=config.checkpoint_policy())
        x = jnp.ones((b, s, config.dim), config.dtype)
        print_saved_residuals(
            lambda x, layer: jnp.sum(jax.tree.leaves(block(x, layer))[0]),
            x, layer)
        lines = capsys.readouterr().out.strip().splitlines()
        return config, [l for l in lines if "from the argument" not in l
                        and "from a constant" not in l]

    config, made = saved("save_flash")
    h, hk, hd = config.n_heads, config.n_kv_heads, config.head_dim
    want = {"flash_q": f"[{b},{h},{s},{hd}]",
            "flash_k": f"[{b},{hk},{s},{hd}]",
            "flash_v": f"[{b},{hk},{s},{hd}]",
            "flash_out": f"[{b},{h},{s},{hd}]", "flash_lse": f"[{b},{h},{s}]",
            "attn_proj": f"[{b},{s},{config.dim}]",
            "mlp_gate": f"[{b},{s},{config.ffn_dim}]"}
    assert set(want) == set(SAVE_FLASH_NAMES)
    if model == "moe":
        del want["mlp_gate"]     # the expert bank names nothing
    assert sorted(l.split(" ")[0] for l in made) == sorted(
        "f32" + v for v in want.values()), made
    for name in ("flash_q", "flash_k", "flash_v", "flash_lse"):
        assert sum(f"named '{name}'" in l for l in made) == 1, made
    assert saved("full")[1] == []     # nothing the block made is kept
