"""The main path's programs, compiled for a TPU v5e that is described and
not attached (section 2 of the on-chip-measurement guide).

Interpret mode and the CPU branches never see what Mosaic and the TPU
compiler refuse: a block that is not a whole (8, 128) tile, a kernel that
needs more scoped VMEM than it may use, a Mosaic call the partitioner is
asked to split, a step that does not fit the chip's HBM. These cases guard
every later PR against them at no chip time. Nothing runs here, so they
say nothing about results or times.

The topology is described inside a module-scoped fixture — never at
import, in a skipif or in parametrize arguments: only one process may load
the TPU's library, every xdist worker imports this file, and only the
worker that is GIVEN it may load the library. All cases live in this one
file for the same reason, and compile in the test's own process.
"""

import os
import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from tony_tpu.models.llama import get_config, llama_init, llama_loss
from tony_tpu.ops.attention import KERNEL_NAMES, flash_attention, kernel_counts
from tony_tpu.ops.rmsnorm import rms_norm

GiB = 2 ** 30
# a v5e chip has 16 GB of HBM; the runtime leaves ~15.75 GiB to a program
HBM_USABLE = 15.75 * GiB
# chip_smoke.py's train phase (MODEL, SEQ_LEN, BATCH there)
SMOKE_BATCH, SMOKE_SEQ = 2, 4096


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topology = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever keeps libtpu away
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep it off around these
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topology
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def fsdp4(topo):
    return Mesh(np.array(topo.devices).reshape(4), ("fsdp",))


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    lowered = jax.jit(fn).lower(*args)
    return lowered, lowered.compile()


def _qkv(b, h, hk, s, sharding, d=128):
    q = _sds((b, h, s, d), jnp.bfloat16, sharding)
    kv = _sds((b, hk, s, d), jnp.bfloat16, sharding)
    return q, kv, kv


def _flash_loss(q, k, v):
    return flash_attention(q, k, v, True).astype(jnp.float32).sum()


# head shapes: llama3_1b_proxy 16/8, llama3_8b 32/8, head_dim 128 both
@pytest.mark.parametrize("heads,kv_heads", [(16, 8), (32, 8)],
                         ids=["1b_proxy", "8b"])
@pytest.mark.parametrize("bwd", [False, True], ids=["fwd", "fwd+bwd"])
def test_flash_compiles_at_model_head_shapes(one_chip, heads, kv_heads, bwd):
    args = _qkv(SMOKE_BATCH, heads, kv_heads, 4096, one_chip)
    fn = (jax.grad(_flash_loss, argnums=(0, 1, 2)) if bwd
          else partial(flash_attention, causal=True))
    _, compiled = _compile(fn, *args)
    got = kernel_counts(compiled.as_text())
    assert got["tony_flash_fwd"] == 1
    assert (got["tony_flash_bwd_dq"], got["tony_flash_bwd_dkv"]) == (
        (1, 1) if bwd else (0, 0))


# serving admission prefills at the raw prompt length (serve/engine.py):
# 3, 37 and 129 were refused by Mosaic before the short-sequence padding
# ("cannot statically prove that index in dimension 1 is a multiple of 8")
@pytest.mark.parametrize("s", [3, 37, 129, 600])
def test_flash_fwd_compiles_at_prompt_lengths(one_chip, s):
    _, compiled = _compile(partial(flash_attention, causal=True),
                           *_qkv(1, 16, 8, s, one_chip))
    assert kernel_counts(compiled.as_text())["tony_flash_fwd"] == 1


@pytest.mark.parametrize("s", [37, 129])
def test_flash_bwd_compiles_at_odd_lengths(one_chip, s):
    """Training at an odd length takes the same pad/slice path."""
    _, compiled = _compile(jax.grad(_flash_loss, argnums=(0, 1, 2)),
                           *_qkv(2, 16, 8, s, one_chip))
    got = kernel_counts(compiled.as_text())
    assert all(got[k] == 1 for k in KERNEL_NAMES[:3]), got


@pytest.mark.parametrize("rows,d,dtype", [
    (SMOKE_BATCH * SMOKE_SEQ, 2048, jnp.bfloat16),   # 1B-proxy train
    (4 * 4096, 4096, jnp.float32),     # 8B width, f32: VMEM-refused at 256
    (3, 2048, jnp.bfloat16),           # decode, 3 rows
    (8, 2048, jnp.bfloat16),           # decode, 8 slots
    (37, 2048, jnp.bfloat16),          # prefill of a 37-token prompt
], ids=["train-1b", "train-8b-f32", "decode-3", "decode-8", "prefill-37"])
def test_rmsnorm_compiles_on_one_chip(one_chip, rows, d, dtype):
    _, compiled = _compile(
        lambda x, w: rms_norm(x, w, 1e-5),
        _sds((rows, d), dtype, one_chip), _sds((d,), jnp.float32, one_chip))
    assert kernel_counts(compiled.as_text())["tony_rmsnorm"] == 1


def test_rmsnorm_and_flash_compile_on_a_4_device_fsdp_mesh(fsdp4):
    """XLA cannot partition a Mosaic call ("Mosaic kernels cannot be
    automatically partitioned. Please wrap the call in a shard_map"): both
    kernels must run on local shards, and the compiled program must still
    hold them — not the jnp branches."""
    rows = NamedSharding(fsdp4, P("fsdp"))
    whole = NamedSharding(fsdp4, P())
    with jax.set_mesh(fsdp4):
        _, norm = _compile(
            lambda x, w: rms_norm(x, w, 1e-5),
            _sds((4, 4096, 2048), jnp.bfloat16, rows),
            _sds((2048,), jnp.float32, whole))
        _, flash = _compile(jax.grad(_flash_loss, argnums=(0, 1, 2)),
                            *_qkv(4, 16, 8, 4096, rows))
    assert kernel_counts(norm.as_text())["tony_rmsnorm"] == 1
    got = kernel_counts(flash.as_text())
    assert all(got[k] == 1 for k in KERNEL_NAMES[:3]), got


def _abstract_params(config, place):
    shapes = jax.eval_shape(partial(llama_init, config),
                            jax.random.PRNGKey(0))
    return jax.tree.map(lambda l: _sds(l.shape, l.dtype, place), shapes)


def test_prefill_and_decode_step_compile_for_1b_proxy(one_chip):
    """The two programs of the serving engine at its default shapes:
    admission of a 512-token prompt, and one decode step of 4 slots x
    2048 tokens (tony.serving.slots / token-budget defaults)."""
    from tony_tpu.models.generate import decode_step, prefill

    config = get_config("llama3_1b_proxy")
    params = _abstract_params(config, one_chip)
    slots, budget = 4, 2048
    _, pre = _compile(
        lambda p, t: prefill(p, t, config, budget), params,
        _sds((1, 512), jnp.int32, one_chip))
    got = kernel_counts(pre.as_text())
    assert got["tony_flash_fwd"] == 1 and got["tony_rmsnorm"] == 3, got
    cache = {name: _sds((config.n_layers, slots, config.n_kv_heads, budget,
                         config.head_dim), jnp.bfloat16, one_chip)
             for name in ("k", "v")}
    _, dec = _compile(
        lambda p, c, t, pos: decode_step(p, config, c, t, pos), params,
        cache, _sds((slots,), jnp.int32, one_chip),
        _sds((slots,), jnp.int32, one_chip))
    assert kernel_counts(dec.as_text())["tony_rmsnorm"] == 3
    for exe in (pre, dec):
        mem = exe.memory_analysis()
        assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                < HBM_USABLE)


def _results_outside_fusions(hlo: str, shaped: re.Pattern) -> list:
    """Instructions of the scheduled program, outside any fused
    computation, whose result `shaped` matches. Parameters and views
    (get-tuple-element, bitcast) move nothing and do not count."""
    view = re.compile(r" = \S+ (parameter|get-tuple-element|bitcast)\(")
    found, fused = [], False
    for line in hlo.splitlines():
        head = re.match(r"^(ENTRY )?%?([\w.\-]+) \(.*\{$", line)
        if head:
            fused = "fused_computation" in head.group(2)
        elif not fused and shaped.search(line) and not view.search(line):
            found.append(line.strip()[:160])
    return found


def _slab_results_outside_fusions(hlo: str, slab: tuple) -> list:
    """Results outside fusions with a cache slab's dimensions (in any
    type, with any leading 1s): each one is a pass over a whole layer of
    the cache in HBM. An int8 cache's slice of a layer's row scales (last
    dimension 1: 1/32 of the slab's bytes) does not count."""
    dims = ",".join(str(d) for d in slab)
    return _results_outside_fusions(
        hlo, re.compile(r" = \w+\[(1,)*%s\]" % dims))


def _weight_results_outside_fusions(hlo: str, params) -> list:
    """Results outside fusions with the dimensions of a layer's weight
    matrix — the last two of any stacked leaf of `params`, in either order
    and in any layout, behind any leading dimensions (one layer sliced out,
    or the whole stack): each one moves a weight before a matmul uses it.
    A matmul that reads its layer in place has no such result: the slice
    is inside its own fusion."""
    matrices = {l.shape[-2:] for l in jax.tree.leaves(params)
                if len(l.shape) >= 3 and min(l.shape[-2:]) >= 256}
    dims = sorted({"%d,%d" % d for m in matrices for d in (m, m[::-1])})
    return _results_outside_fusions(
        hlo, re.compile(r" = \w+\[(\d+,)*(%s)\]" % "|".join(dims)))


def test_the_weight_helper_finds_the_q_projections_slice_and_copy():
    """The helper on the lines PR 42 took out of the chat-steady decode
    program (text of the parent's compile, shortened): the slice of `wq`'s
    layer out of the stack and its re-laid-out copy are found, 0.71 and
    0.55 ms a step on the chip; the bitcast, the matmul over the copy and
    a matmul that slices inside its own fusion are not."""
    hlo = """\
%fused_computation.7 (param_0: bf16[16,4096,4096], param_1: s32[]) -> bf16[32,4096] {
  %dynamic-slice.3 = bf16[1,4096,4096]{2,1,0} dynamic-slice(%param_0, %param_1)
  ROOT %convolution.35 = bf16[32,4096]{1,0} convolution(%p, %dynamic-slice.3)
}
%wide.region_0.clone (wide.arg: (s32[], bf16[16,4096,4096])) -> (s32[]) {
  %get-tuple-element.597 = bf16[16,4096,4096]{2,1,0} get-tuple-element(%wide.arg), index=1
  %constant_dynamic-slice_fusion.14 = bf16[1,4096,4096]{2,1,0:T(8,128)(2,1)S(1)} fusion(%get-tuple-element.597, %i), kind=kLoop
  %copy.50 = bf16[1,4096,4096]{1,2,0:T(8,128)(2,1)S(1)} copy(%constant_dynamic-slice_fusion.14)
  %bitcast.145 = bf16[32,128,4096]{2,1,0} bitcast(%copy.50)
  %convert_bitcast_fusion.2 = f32[32,32,1,128]{3,0,1,2} fusion(%bitcast.145, %tony_rmsnorm.12), kind=kOutput
  %copy.53 = bf16[1,1024,4096]{2,1,0} copy(%k)
  %fusion.99 = bf16[32,1,4096]{2,0,1} fusion(%tony_rmsnorm.12, %get-tuple-element.597, %i), kind=kOutput
}
"""
    params = {"layers": {"wq": _sds((16, 4096, 4096), jnp.bfloat16, None),
                         "wk": _sds((16, 4096, 1024), jnp.bfloat16, None),
                         "attn_norm": _sds((16, 4096), jnp.float32, None)}}
    found = _weight_results_outside_fusions(hlo, params)
    assert [line.split(" = ")[0] for line in found] == [
        "%constant_dynamic-slice_fusion.14", "%copy.50", "%copy.53"]


def _chat_steady_cell(one_chip, quant):
    """benchmark/configs/mistral-7b-serve.json as the program has it:
    Mistral-7B widths, 16 layers, 32 slots x 2048 tokens, a bf16 or an
    int8 cache; abstract weights and cache."""
    from tony_tpu.models.llama import LlamaConfig

    config = LlamaConfig(vocab_size=32000, dim=4096, n_layers=16, n_heads=32,
                         n_kv_heads=8, ffn_dim=14336, max_seq=4096,
                         rope_theta=10000.0)
    slots, budget = 32, 2048
    slab = (slots, config.n_kv_heads, budget, config.head_dim)
    kv = jnp.int8 if quant else jnp.bfloat16
    cache = {name: _sds((config.n_layers,) + slab, kv, one_chip)
             for name in ("k", "v")}
    if quant:
        for name in ("k_scale", "v_scale"):
            cache[name] = _sds((config.n_layers,) + slab[:-1] + (1,),
                               jnp.float32, one_chip)
    cache_bytes = sum(int(np.prod(c.shape)) * c.dtype.itemsize
                      for c in cache.values())
    return (config, _abstract_params(config, one_chip), cache, cache_bytes,
            slots, slab)


@pytest.mark.parametrize("per_row", [True, False], ids=["per-row", "scalar"])
@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
def test_decode_step_updates_the_cache_in_place_at_the_cell_shapes(
        one_chip, quant, per_row):
    """The serving engine's decode program at the chat-steady cell's
    shapes (benchmark/configs/mistral-7b-serve.json: Mistral-7B widths, 16
    layers, 32 slots x 2048 tokens, cache donated). Before PR 27 it held a
    second whole cache in temporaries (4.83 GB) and made three passes over
    the cache per token: `dynamic-slice_bitcast_fusion`, `copy` and
    `dynamic-update-slice`, each with a slab-shaped result, per layer
    (41 of 58.8 ms a step on the chip). Now the layer loop only reads the
    cache and the rows are written in place after it."""
    from tony_tpu.serve.engine import _decode_sample_step

    config, params, cache, cache_bytes, slots, slab = _chat_steady_cell(
        one_chip, quant)
    compiled = _decode_sample_step.lower(
        params, config, cache, _sds((slots,), jnp.int32, one_chip),
        _sds((slots,) if per_row else (), jnp.int32, one_chip),
        _sds((2,), jnp.uint32, one_chip), _sds((), jnp.int32, one_chip),
        0.0, 0, 1.0).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 256e6, mem.temp_size_in_bytes
    assert mem.alias_size_in_bytes >= cache_bytes
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_USABLE
    assert _slab_results_outside_fusions(compiled.as_text(), slab) == []


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
def test_decode_step_reads_the_cache_through_the_length_aware_kernel(
        one_chip, quant):
    """The decode program as the engine calls it since PR 35 (positions
    and attend lengths, two host arrays) at the chat-steady cell's shapes:
    `tony_decode_read` is in the compiled program, once (one layer body,
    scanned), it takes the whole cache where it lies — the temporaries
    stay far under one layer's K slab (134 MB bf16; 0.3 MB measured here,
    34 MB with an int8 cache, whose row scales change layout once a step)
    — and weights, cache and temporaries fit the chip (11.8 GB of
    arguments in bf16). Before it the step's two largest operations were
    float32 reads of every layer's whole slab (5.6 of 17.0 ms on the chip:
    PERF.md, PR 35)."""
    from tony_tpu.serve.engine import _decode_sample_step

    config, params, cache, cache_bytes, slots, slab = _chat_steady_cell(
        one_chip, quant)
    per_slot = _sds((slots,), jnp.int32, one_chip)
    compiled = _decode_sample_step.lower(
        params, config, cache, per_slot, per_slot,
        _sds((2,), jnp.uint32, one_chip), _sds((), jnp.int32, one_chip),
        0.0, 0, 1.0, attend=per_slot).compile()
    text = compiled.as_text()
    assert len(re.findall(r"%tony_decode_read[.\d]* = [^=]*? custom-call\(",
                          text)) == 1, text.count("tony_decode_read")
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < (48e6 if quant else 8e6), \
        mem.temp_size_in_bytes
    assert mem.alias_size_in_bytes >= cache_bytes
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_USABLE
    assert _slab_results_outside_fusions(text, slab) == []


@pytest.mark.parametrize("program", ["decode", "admission-384"])
def test_serving_programs_read_each_layers_weights_in_place(one_chip,
                                                            program):
    """The two programs of the chat-steady cell, at its shapes: the decode
    step as the engine calls it and the admission of the mix's median
    prompt. No result outside a fusion has a layer's weight shape: every
    projection is one matmul that slices its layer out of the stack inside
    its own fusion. Before PR 42 the split into heads was folded into the
    Q and K matmuls (and V's, in the admission), which then wanted the
    weight with the contracted dimension minor: each layer of each step
    sliced `wq`'s 33.5 MB out of the stack and re-laid it out before a
    0.19 ms matmul (`constant_dynamic-slice_fusion.14`, `copy.50`: 1.45 ms
    of an 11.4 ms step on the chip against `wo`'s 0.71 for the same bytes;
    PERF.md, PR 42). `qkv_proj(split_on_result=True)` is the repair."""
    from tony_tpu.serve.engine import _admit_step, _decode_sample_step

    config, params, cache, _, slots, _ = _chat_steady_cell(one_chip, False)
    per_slot = _sds((slots,), jnp.int32, one_chip)
    scalar = _sds((), jnp.int32, one_chip)
    key = _sds((2,), jnp.uint32, one_chip)
    if program == "decode":
        lowered = _decode_sample_step.lower(
            params, config, cache, per_slot, per_slot, key, scalar,
            0.0, 0, 1.0, attend=per_slot)
    else:
        lowered = _admit_step.lower(
            params, config, cache, per_slot, _sds((384,), jnp.int32,
                                                  one_chip),
            scalar, key, scalar, 0.0, 0, 1.0, False, scalar, False)
    compiled = lowered.compile()
    assert _weight_results_outside_fusions(compiled.as_text(), params) == []
    assert compiled.memory_analysis().temp_size_in_bytes < 8e6


def _compile_train_step(config, one_chip, dump_to, batch):
    """(lowered, compiled, bytes) of the trainer's own step for `config`
    (train/trainer.py: adamw under a warm-up cosine schedule, bf16 state,
    donated) on `batch` (names -> shapes). The bytes are the compile's own
    buffer assignment — the arguments plus one preallocated heap of
    temporaries, "Total bytes used" of the memory-usage report XLA dumps —
    which is the count the chip obeys: a ballast of (15.75 GiB - this)
    runs beside the step and one of 0.25 GiB more does not, for four steps
    of four sizes (tools/train_ballast.py; PERF.md §6, PR 47).
    `memory_analysis()`'s arguments + temporaries adds the layer scan's
    stacked residuals a second time and reads 2.0 to 3.9 GiB higher."""
    import optax

    from tony_tpu.train.step import make_train_step

    params = _abstract_params(config, one_chip)
    optimizer = optax.adamw(
        optax.warmup_cosine_decay_schedule(0.0, 3e-4, 10, 100),
        weight_decay=0.01)
    opt_state = jax.tree.map(
        lambda l: _sds(l.shape, l.dtype, one_chip),
        jax.eval_shape(optimizer.init, params))
    batch = {k: _sds(shape, jnp.int32, one_chip)
             for k, shape in batch.items()}
    step = make_train_step(partial(llama_loss, config=config), optimizer,
                           jit=False)
    lowered = jax.jit(step, donate_argnums=(0, 1)).lower(
        params, opt_state, batch)
    compiled = lowered.compile(
        compiler_options={"xla_dump_to": str(dump_to)})
    (report,) = dump_to.glob("*jit_train_step*memory-usage-report.txt")
    used = int(re.search(r"Total bytes used: (\d+)",
                         report.read_text()).group(1))
    return lowered, compiled, used


# flash fwd once (the remat replay reuses the saved residuals), dq and
# dk/dv once, RMSNorm twice a layer forward + twice in the replay + the
# final norm — in the lowered step and in the compiled one
TRAIN_STEP_KERNELS = {"tony_flash_fwd": 1, "tony_flash_bwd_dq": 1,
                      "tony_flash_bwd_dkv": 1, "tony_rmsnorm": 5,
                      "tpu_custom_call": 8}


def test_whole_1b_proxy_train_step_holds_the_kernels_and_fits(one_chip,
                                                              tmp_path):
    """The step chip_smoke.py's train phase runs, at the smoke's batch. A
    passing compile does NOT prove the program fits (batch 8 compiles
    too), so the size is read from the buffer assignment
    (`_compile_train_step`). Before PR 47 this test summed
    `memory_analysis()`'s arguments and temporaries, "an upper bound:
    batch 4 sums to 16.1 GiB and still ran on the chip" (CHANGES.md,
    PR 22); by that sum this step now reads 16.61 GiB and by the chip's
    count 13.86 (10.13 before `save_flash` kept q, k, v, the projected
    output and `w_gate`'s result: 224 MB a layer over 16 layers)."""
    lowered, compiled, used = _compile_train_step(
        get_config("llama3_1b_proxy"), one_chip, tmp_path,
        {k: (SMOKE_BATCH, SMOKE_SEQ) for k in ("inputs", "targets")})
    assert kernel_counts(lowered.as_text()) == TRAIN_STEP_KERNELS
    assert kernel_counts(compiled.as_text()) == TRAIN_STEP_KERNELS
    assert used < HBM_USABLE, f"{used / GiB:.2f} GiB"
    # and with room: at least 1 GiB under usable, for a process that also
    # holds two prefetched batches of 64 KB and whatever the runtime keeps
    assert used < HBM_USABLE - 1 * GiB, f"{used / GiB:.2f} GiB"


def test_train_4k_cells_step_fits_and_replays_no_saved_matmul(one_chip,
                                                              tmp_path):
    """The train-4k cell's own step (the literals of
    benchmark/configs/mistral-7b-train.json: Mistral-7B widths, 5 layers,
    adamw, donated, `xent_chunk` 1024, `remat_policy` "save_flash"; the
    batch as benchmark/lib/traffic.py hands it over, 2 rows of 4096 + 1
    tokens). (a) It takes 14.22 GiB by the count the chip obeys and
    must stay at or under 15.0, 0.75 GiB under usable (11.89 before PR 47;
    `w_up`'s result on top would read 15.33, which is why the policy stops
    where it does). (b) The kernels are called as before. (c) The replay
    holds no matmul whose result the policy saves: XLA counts 13.88e12
    flops for the step (loop bodies once) where the parent's counted
    15.53e12 — one layer's `wq`/`wk`/`wv` (0.4125e12), `wo` (0.275e12) and
    `w_gate` (0.962e12) are gone from the backward loop's body; `w_up`
    (0.962e12) stays."""
    from tony_tpu.models.llama import LlamaConfig

    config = LlamaConfig(
        vocab_size=32000, dim=4096, n_layers=5, n_heads=32, n_kv_heads=8,
        ffn_dim=14336, max_seq=4096, rope_theta=10000.0, norm_eps=1e-5,
        xent_chunk=1024, remat=True, remat_policy="save_flash")
    lowered, compiled, used = _compile_train_step(
        config, one_chip, tmp_path, {"tokens": (2, 4097)})
    assert used <= 15.0 * GiB, f"{used / GiB:.2f} GiB"
    assert kernel_counts(lowered.as_text()) == TRAIN_STEP_KERNELS
    assert kernel_counts(compiled.as_text()) == TRAIN_STEP_KERNELS
    flops = compiled.cost_analysis()["flops"]
    assert flops <= 13.90e12, f"{flops / 1e12:.3f}e12"


# -- layers of several kinds: the sala-longdoc cell's two programs ---------

def _sala_cell(one_chip):
    """benchmark/configs/minicpm-sala-serve.json as the program has it:
    MiniCPM-SALA widths, 4 periods of one sparse + three lightning layers,
    16 slots x 36864 tokens; abstract weights and cache."""
    from tony_tpu.models import sala

    config = sala.SalaConfig(
        n_layers=16, max_seq=36864,
        mixer_types=((sala.SPARSE,) + (sala.LIGHTNING,) * 3) * 4)
    slots, budget = 16, 36864
    params = jax.tree.map(
        lambda l: _sds(l.shape, l.dtype, one_chip),
        jax.eval_shape(partial(sala.sala_init, config),
                       jax.random.PRNGKey(0)))
    cache = jax.tree.map(
        lambda l: _sds(l.shape, l.dtype, one_chip),
        jax.eval_shape(lambda: sala.empty_cache(config, slots, budget)))
    cache_bytes = sum(int(np.prod(c.shape)) * c.dtype.itemsize
                      for c in cache.values())
    return config, params, cache, cache_bytes, slots, budget


@pytest.mark.parametrize("mask", ["engine-mask", "every-slot-rides"])
def test_sala_decode_step_updates_its_cache_by_kind_in_place(one_chip, mask):
    """One decode step of the hybrid model at the cell's shapes, with the
    engine's riding mask (`attend`, which tells `tony_lightning_step`
    whose state to move) and without it (offline `generate`): the K/V
    rows, the compressed keys and the lightning states (2.9 GB together)
    are aliased in and out, nothing cache-sized is copied (no K/V slab,
    no copy of the 0.4 GB of states, no transposed stack of weights: each
    of those was there before it was repaired, 1.2 to 1.7 GB of
    temporaries), and the step holds each kind's block once: its program
    text is 0.50 MB, three times that of Mistral's one block (0.17 MB: two
    kinds of block, the selection, two kernels), where sixteen unrolled
    layers would be some sixteen times it. The selection is the kernel
    `tony_sparse_select`, which takes a rider's compressed keys out of the
    whole `ck` leaf (no layer's keys are sliced out) and ranks its block
    scores: the program sorts nothing (before PR 44 the period's body held
    two sorts, `lax.top_k`'s full sort of 576 pairs a row and the ids')."""
    from tony_tpu.serve.engine import _decode_sample_step

    config, params, cache, cache_bytes, slots, budget = _sala_cell(one_chip)
    compiled = _decode_sample_step.lower(
        params, config, cache, _sds((slots,), jnp.int32, one_chip),
        _sds((slots,), jnp.int32, one_chip),
        _sds((2,), jnp.uint32, one_chip), _sds((), jnp.int32, one_chip),
        0.0, 0, 1.0, attend=_sds((slots,), jnp.int32, one_chip)
        if mask == "engine-mask" else None).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 64e6, mem.temp_size_in_bytes
    assert mem.alias_size_in_bytes >= cache_bytes
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_USABLE
    text = compiled.as_text()
    slab = (slots, config.n_kv_heads, budget, config.head_dim)
    assert _slab_results_outside_fusions(text, slab) == []
    keys = slab[:2] + (budget // config.sparse.kernel_stride, slab[3])
    assert _slab_results_outside_fusions(text, keys) == []
    assert _weight_results_outside_fusions(text, params) == []
    for kernel in ("tony_sparse_select", "tony_sparse_read",
                   "tony_lightning_step"):
        assert kernel in text, kernel
    assert " sort(" not in text and "top_k" not in text.lower()
    # two kinds of block, each once: not sixteen unrolled layers
    assert text.count("tony_lightning_step") < 8
    assert len(text) < 1.2e6, len(text)


def test_sala_admission_of_32768_tokens_fits_beside_weights_and_cache(
        one_chip):
    """The longest admission of the cell (a 32768-token prompt, batch 1):
    the block-sparse and chunked-lightning kernels lower, the temporaries
    stay under 2.5 GB (the MLP, the norms and the rotation run over row
    blocks; heads are never transposed in float32), and weights, cache
    and temporaries fit the chip together."""
    from tony_tpu.serve.engine import _admit_step

    config, params, cache, cache_bytes, slots, _ = _sala_cell(one_chip)
    compiled = _admit_step.lower(
        params, config, cache, _sds((slots,), jnp.int32, one_chip),
        _sds((32768,), jnp.int32, one_chip), _sds((), jnp.int32, one_chip),
        _sds((2,), jnp.uint32, one_chip), _sds((), jnp.int32, one_chip),
        0.0, 0, 1.0, False, _sds((), jnp.int32, one_chip), False).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 2.5e9, mem.temp_size_in_bytes
    assert mem.alias_size_in_bytes >= cache_bytes
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_USABLE
    text = compiled.as_text()
    for kernel in ("tony_sparse_attn", "tony_lightning_chunk"):
        assert kernel in text, kernel


# -- conv + attention layers with experts: the lfm2-longgen cell's programs --

def _lfm2_cell(one_chip):
    """benchmark/configs/lfm2-24b-a2b-serve.json as the program has it:
    LFM2-24B-A2B widths (heads of 64, 64 experts of 1536, top-4), the
    first 10 published layers, 64 slots x 8192 tokens; abstract weights
    and cache."""
    from tony_tpu.models import lfm2

    config = lfm2.Lfm2Config(n_layers=10, max_seq=8192,
                             layer_types=lfm2.Lfm2Config.layer_types[:10])
    slots, budget = 64, 8192
    params = jax.tree.map(
        lambda l: _sds(l.shape, l.dtype, one_chip),
        jax.eval_shape(partial(lfm2.lfm2_init, config),
                       jax.random.PRNGKey(0)))
    cache = jax.tree.map(
        lambda l: _sds(l.shape, l.dtype, one_chip),
        jax.eval_shape(lambda: lfm2.empty_cache(config, slots, budget)))

    def nbytes(tree):
        return sum(int(np.prod(c.shape)) * c.dtype.itemsize
                   for c in jax.tree.leaves(tree))
    return config, params, cache, nbytes(params), nbytes(cache), slots


def test_lfm2_decode_step_reads_experts_in_place_at_heads_of_64(one_chip):
    """The 64-slot decode step at the cell's shapes: weights 10.53 GB and
    cache 2.15 GB + 13 MB of float32 conv states are arguments, the cache aliased in
    and out; `tony_expert_matmul` takes the expert stacks where they lie
    (no layer's 0.6 GB of experts is sliced out: the temporaries stay
    under 64 MB) and `tony_decode_read` compiles at heads of 64; each kind
    of layer body is held once; the step returns its counts."""
    from tony_tpu.serve.engine import _decode_sample_step

    config, params, cache, weights, cache_bytes, slots = _lfm2_cell(one_chip)
    assert abs(weights - 10.534e9) < 0.01e9 and abs(
        cache_bytes - 2.1601e9) < 0.001e9
    per_slot = _sds((slots,), jnp.int32, one_chip)
    lowered = _decode_sample_step.lower(
        params, config, cache, per_slot, per_slot,
        _sds((2,), jnp.uint32, one_chip), _sds((), jnp.int32, one_chip),
        0.0, 0, 1.0, attend=per_slot)
    assert [o.shape for o in jax.tree.leaves(lowered.out_info)][-1] == (2,)
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 64e6, mem.temp_size_in_bytes
    assert mem.alias_size_in_bytes >= cache_bytes
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_USABLE
    text = compiled.as_text()
    for kernel in ("tony_expert_matmul", "tony_decode_read"):
        assert kernel in text, kernel
    # the attention body's pair and the conv body's pair, not 2 x 8 layers
    assert len(re.findall(
        r"%tony_expert_matmul[.\d]* = [^=]*? custom-call\(", text)) == 4


@pytest.mark.parametrize("prompt,temp_limit", [(256, 0.1e9), (4096, 1.5e9)])
def test_lfm2_admission_fits_beside_weights_and_cache(one_chip, prompt,
                                                      temp_limit):
    """The shortest and the longest admission of the cell (batch 1): flash
    attention at heads of 64 and the grouped matmul at the admission's
    tile (16 rows for 256 tokens, 256 for 4096) lower, and weights, cache
    and temporaries fit the chip together: slots stayed 64."""
    from tony_tpu.serve.engine import _admit_step

    config, params, cache, weights, cache_bytes, slots = _lfm2_cell(one_chip)
    compiled = _admit_step.lower(
        params, config, cache, _sds((slots,), jnp.int32, one_chip),
        _sds((prompt,), jnp.int32, one_chip), _sds((), jnp.int32, one_chip),
        _sds((2,), jnp.uint32, one_chip), _sds((), jnp.int32, one_chip),
        0.0, 0, 1.0, False, _sds((), jnp.int32, one_chip), False).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < temp_limit, mem.temp_size_in_bytes
    assert mem.alias_size_in_bytes >= cache_bytes
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_USABLE
    text = compiled.as_text()
    for kernel in ("tony_expert_matmul", "tony_flash_fwd"):
        assert kernel in text, kernel
