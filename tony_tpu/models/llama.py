"""Llama-family transformer, TPU-first.

Design choices (and why they differ from a GPU/torch translation):
- **Stacked layers + lax.scan**: all L layers' weights are stacked on a
  leading axis and the block runs under `lax.scan` — one trace, one compile,
  regardless of depth (no Python-loop unrolling; XLA-friendly control flow).
- **jax.checkpoint on the block**: rematerialize activations per layer,
  trading MXU FLOPs for HBM — the standard TPU memory lever.
- **bf16 params / f32 stats**: matmuls run on the MXU in bf16 with f32
  accumulation (`preferred_element_type` inside the ops package); norms and
  softmax statistics stay f32.
- **logical sharding axes** declared next to the params
  (`llama_param_axes`): embed/mlp dims shard over fsdp+tp, batch over
  (dp, fsdp), sequence over sp; `parallel.sharding.constrain` applies them
  against whatever mesh is ambient, so the same code runs single-chip or on
  a pod.
- **GQA + RoPE + SwiGLU + RMSNorm** matching the Llama-3 architecture; the
  8B preset mirrors the BASELINE target config.
- Attention dispatch: ring attention over the `sp` axis when the ambient
  mesh shards sequence (long-context), pallas flash attention otherwise.

Equivalent role in the reference: tony-examples' model zoo (SURVEY.md §2.2),
re-targeted at the Llama-3-8B JAX pretrain named in BASELINE.json.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from tony_tpu.ops.attention import FLASH_RESIDUAL_NAMES, flash_attention
from tony_tpu.ops.rmsnorm import rms_norm
from tony_tpu.ops.rope import apply_rope, rope_frequencies
from tony_tpu.parallel.ring import ring_attention
from tony_tpu.parallel.sharding import constrain

Params = dict[str, Any]

# what remat_policy="save_flash" keeps of a block (LlamaConfig's comment)
SAVE_FLASH_NAMES = FLASH_RESIDUAL_NAMES + ("attn_proj", "mlp_gate")


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128_256
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    ffn_dim: int = 14_336
    max_seq: int = 8192
    rope_theta: float = 500_000.0
    # Llama-3.1-style long-context RoPE rescale (ops/rope.py): >1 slows
    # the low-frequency components so a model trained at rope_orig_max_seq
    # extends to factor-times-longer contexts (the ring-attention regime);
    # 0 = off
    rope_scaling_factor: float = 0.0
    # pretrained context window the rescale anchors to; 0 = this config's
    # max_seq (set explicitly when max_seq itself was extended)
    rope_orig_max_seq: int = 0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    remat: bool = True
    # remat policy. "save_flash" keeps, of every block, what the chip can
    # keep and the backward would otherwise re-make (SAVE_FLASH_NAMES; the
    # name says less than it saves, a rename waits for the benchmark's
    # config file): all five residuals of the flash kernel (q, k, v, out,
    # lse, named in ops/attention.py), the attention sublayer's projected
    # output, and w_gate's result. The backward replay of a block then
    # runs no flash fwd kernel, no wq/wk/wv/wo/w_gate matmul, no RoPE and
    # no split into heads; it still recomputes the two norms, w_up, the
    # SwiGLU product and the residual adds. Per row of the batch*seq in
    # bf16: 2*(2*H*hd + 2*Hkv*hd + dim + ffn) bytes + 4*H for lse — 449
    # MiB a layer at the train cell's 8192 rows of Mistral's widths, for
    # 8.9 % more tokens a second there than out and lse alone give
    # (PERF.md §6, PR 47, with the memory count the chip obeys; w_up's
    # result as well would not leave 0.75 GiB free at 5 layers).
    # "full" rematerializes everything (minimum memory)
    remat_policy: str = "save_flash"
    # sequence-parallel flavor when the mesh shards seq: "ring" streams K/V
    # chunks over ICI neighbors (long context); "ulysses" swaps to
    # head-sharding with two all-to-alls (DCN-friendly, needs heads % sp == 0)
    sp_mode: str = "ring"
    # sequence-chunk size for the fused LM-head cross-entropy (ops/xent.py):
    # caps logits memory at O(B*chunk*vocab) instead of O(B*S*vocab) fwd AND
    # bwd. 0 = unfused full-logits path (tiny/test configs, and inference
    # always materializes logits via llama_forward).
    xent_chunk: int = 0

    def __post_init__(self):
        if self.remat_policy not in ("save_flash", "full"):
            raise ValueError(
                f"remat_policy must be 'save_flash' or 'full', got "
                f"{self.remat_policy!r}")

    def checkpoint_policy(self):
        """The jax.checkpoint policy for this config (None = save none)."""
        if self.remat_policy == "save_flash":
            return jax.checkpoint_policies.save_only_these_names(
                *SAVE_FLASH_NAMES)
        return None

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    def flops_per_token(self, seq_len: Optional[int] = None) -> float:
        """Training FLOPs/token: forward + backward (3x forward) of the
        matmuls and of causal attention. The embedding lookup, the norms
        and recomputed (rematerialised) operations are not counted — the
        benchmark's count (`mfu_pct` in PERF.md), to the last digit."""
        s = seq_len or self.max_seq
        # QK^T and PV, 4*s*hd a head forward, halved under the causal mask
        attn = 6 * self.n_layers * self.n_heads * self.head_dim * s
        return 6.0 * self.matmul_params() + attn

    def _layer_matmul_params(self) -> int:
        """One block's attention projections and SwiGLU MLP."""
        d, hd = self.dim, self.head_dim
        attn = d * (self.n_heads * hd) + 2 * d * (self.n_kv_heads * hd) \
            + (self.n_heads * hd) * d
        return attn + 3 * d * self.ffn_dim

    def num_params(self) -> int:
        d, v = self.dim, self.vocab_size
        per_layer = self._layer_matmul_params() + 2 * d
        return v * d + self.n_layers * per_layer + d + d * v

    def matmul_params(self) -> int:
        """Weights a token is multiplied by: every block's projections
        and MLP, and the output head. The embedding table is a lookup and
        the norms are elementwise."""
        return (self.n_layers * self._layer_matmul_params()
                + self.dim * self.vocab_size)


# Presets. llama3_8b mirrors BASELINE.json's target model; the tiny/bench
# configs scale it down for tests and single-chip benchmarking.
PRESETS = {
    "llama3_8b": LlamaConfig(xent_chunk=1024),
    # Llama-3-70B geometry: the ">16B models need pp" regime
    # (docs/SCALING.md) — compiler-validated on a v5p-128 topology by
    # tools/aot_8b.py --model llama3_70b
    "llama3_70b": LlamaConfig(dim=8192, n_layers=80, n_heads=64,
                              n_kv_heads=8, ffn_dim=28_672,
                              xent_chunk=1024),
    "llama3_1b_proxy": LlamaConfig(vocab_size=32_000, dim=2048, n_layers=16,
                                   n_heads=16, n_kv_heads=8, ffn_dim=8192,
                                   max_seq=4096, xent_chunk=1024),
    "bench_350m": LlamaConfig(vocab_size=32_000, dim=1024, n_layers=16,
                              n_heads=16, n_kv_heads=8, ffn_dim=4096,
                              max_seq=2048, xent_chunk=1024),
    "tiny": LlamaConfig(vocab_size=256, dim=64, n_layers=2, n_heads=4,
                        n_kv_heads=2, ffn_dim=128, max_seq=128,
                        dtype=jnp.float32, remat=False),
}


def get_config(name: str, **overrides) -> LlamaConfig:
    return replace(PRESETS[name], **overrides)


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------

def llama_init(config: LlamaConfig, key: jax.Array) -> Params:
    """Scaled-normal init; per-layer weights stacked on a leading axis."""
    d, f = config.dim, config.ffn_dim
    hd, nh, nkv = config.head_dim, config.n_heads, config.n_kv_heads
    L = config.n_layers
    k_embed, k_out, k_layers = jax.random.split(key, 3)

    def normal(key, shape, scale):
        return (jax.random.normal(key, shape, jnp.float32) * scale).astype(
            config.dtype)

    ks = jax.random.split(k_layers, 7)
    scale_in = d ** -0.5
    scale_ffn = f ** -0.5
    return {
        "embed": normal(k_embed, (config.vocab_size, d), 1.0),
        "layers": {
            "wq": normal(ks[0], (L, d, nh * hd), scale_in),
            "wk": normal(ks[1], (L, d, nkv * hd), scale_in),
            "wv": normal(ks[2], (L, d, nkv * hd), scale_in),
            "wo": normal(ks[3], (L, nh * hd, d), scale_in),
            "w_gate": normal(ks[4], (L, d, f), scale_in),
            "w_up": normal(ks[5], (L, d, f), scale_in),
            "w_down": normal(ks[6], (L, f, d), scale_ffn),
            "attn_norm": jnp.ones((L, d), jnp.float32),
            "mlp_norm": jnp.ones((L, d), jnp.float32),
        },
        "final_norm": jnp.ones((d,), jnp.float32),
        "output": normal(k_out, (d, config.vocab_size), scale_in),
    }


def llama_param_axes(config: LlamaConfig) -> Params:
    """Logical sharding axes, same tree shape as the params."""
    return {
        "embed": ("vocab", "embed"),
        "layers": {
            "wq": ("layers", "embed", "heads"),
            "wk": ("layers", "embed", "kv_heads"),
            "wv": ("layers", "embed", "kv_heads"),
            "wo": ("layers", "heads", "embed"),
            "w_gate": ("layers", "embed", "mlp"),
            "w_up": ("layers", "embed", "mlp"),
            "w_down": ("layers", "mlp", "embed"),
            "attn_norm": ("layers", "norm"),
            "mlp_norm": ("layers", "norm"),
        },
        "final_norm": ("norm",),
        "output": ("embed", "vocab"),
    }


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _attention_dispatch(q, k, v, config: LlamaConfig):
    """Sequence-parallel attention (ring or ulysses per config.sp_mode)
    when the ambient mesh shards the sequence axis, flash attention
    otherwise. The pallas kernels themselves handle multi-chip meshes by
    running inside their own batch/heads shard_map (ops/attention.py
    _kernel_shard_axes) — a Mosaic custom call cannot be partitioned by
    XLA's Auto partitioner."""
    mesh = jax.sharding.get_abstract_mesh()
    sp = mesh.shape.get("sp", 1)
    if sp > 1:
        if config.sp_mode == "ulysses":
            from tony_tpu.ops.attention import _gqa_broadcast
            from tony_tpu.parallel.ulysses import ulysses_attention

            # ulysses all-to-alls the head dim, so every rank's head slice
            # needs its own K/V: broadcast GQA groups up front. Ring needs
            # no broadcast — its per-chunk flash streams narrow K/V
            # natively, keeping ppermute bytes at 1/group of the broadcast
            # layout (fwd K/V and bwd dK/dV alike).
            k, v = _gqa_broadcast(q, k, v)
            inner = partial(ulysses_attention, axis_name="sp", causal=True)
        else:
            inner = partial(ring_attention, axis_name="sp", causal=True)
        if "sp" in mesh.manual_axes:
            # already inside a manual-sp region (the pp pipeline widens
            # its shard_map to {pp, sp}): call the collective attention
            # DIRECTLY — the kernel dispatch (ops/attention.py
            # _shard_kernel_call) handles any remaining Auto axes
            return inner(q, k, v)
        # manual over the WHOLE mesh: the per-chunk flash is a Mosaic
        # call, and jax only lowers those in a fully-manual context
        # (ops/attention.py _shard_kernel_call). Batch rides (dp, fsdp),
        # heads ride tp, sequence rides sp; axes the operands don't
        # shard on are left unmentioned (replicated — Auto semantics)
        from tony_tpu.ops.attention import _kernel_shard_axes
        batch_axes, tp_axes = _kernel_shard_axes(q.shape[0], q.shape[1],
                                                 k.shape[1])
        if tp_axes and config.sp_mode == "ulysses":
            # ulysses splits the LOCAL head count over sp; pre-sharding
            # heads over tp tightens its divisibility to (H/tp) % sp —
            # fall back to replicated heads when that fails rather than
            # raising on a config the un-tp'd path accepted
            tp = mesh.shape["tp"]
            if (q.shape[1] // tp) % sp != 0:
                tp_axes = ()
        spec = jax.sharding.PartitionSpec(
            batch_axes if batch_axes else None,
            "tp" if tp_axes else None, "sp")
        f = jax.shard_map(
            inner, in_specs=(spec, spec, spec), out_specs=spec,
            axis_names=set(mesh.axis_names))
        return f(q, k, v)
    return flash_attention(q, k, v, True)


def rope_tables(config: LlamaConfig, seq: int):
    """(cos, sin) tables honoring the config's theta and long-context
    scaling; the single rope entry point for every model path (training,
    pipelined, MoE, prefill/decode)."""
    return rope_frequencies(
        config.head_dim, seq, config.rope_theta,
        scaling_factor=config.rope_scaling_factor,
        orig_max_seq=config.rope_orig_max_seq or config.max_seq)


def qkv_proj(h: jax.Array, layer: Params, config: LlamaConfig,
             split_on_result: bool = False
             ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """(B, S, D) -> q (B,H,S,hd), k/v (B,Hkv,S,hd) — pre-RoPE. Shared by
    the training forward here and the KV-cache decode (models/generate.py)
    so architecture changes land in one place.

    `split_on_result` is the serving programs' rule (every caller in
    models/generate.py and serve/kvcache.py passes it; models/sala.py
    states the same rule for its own projections): a projection's heads
    are split on its result, never on its weight. A barrier keeps the
    reshape and the transpose below out of the matmul: folded into it, the
    compiler batches the matmul over heads and wants the WEIGHT with the
    contracted dimension minor, so with a few rows of activations (a
    decode step's 32, an admission's 1536 at most) it slices the layer out
    of the stacked weights and re-lays-out all of it, every layer of every
    step, to save transposing the rows. The trainer does not pass it: at
    8192 rows the activations are the large operand, the copy is lost in
    the step, and a barrier would only take fusion freedom from it."""
    b, s, _ = h.shape
    nh, nkv, hd = config.n_heads, config.n_kv_heads, config.head_dim
    q = jnp.einsum("bsd,dh->bsh", h, layer["wq"])
    k = jnp.einsum("bsd,dh->bsh", h, layer["wk"])
    v = jnp.einsum("bsd,dh->bsh", h, layer["wv"])
    if split_on_result:
        q, k, v = (lax.optimization_barrier(x) for x in (q, k, v))
    q = q.reshape(b, s, nh, hd).transpose(0, 2, 1, 3)      # (B,H,S,hd)
    k = k.reshape(b, s, nkv, hd).transpose(0, 2, 1, 3)
    v = v.reshape(b, s, nkv, hd).transpose(0, 2, 1, 3)
    return q, k, v


def swiglu_mlp(h: jax.Array, layer: Params) -> jax.Array:
    """SwiGLU feed-forward; shared with models/generate.py."""
    gate = jnp.einsum("bsd,df->bsf", h, layer["w_gate"])
    up = jnp.einsum("bsd,df->bsf", h, layer["w_up"])
    return jnp.einsum("bsf,fd->bsd", jax.nn.silu(gate) * up,
                      layer["w_down"])


def attention_sublayer(h: jax.Array, layer: Params, config: LlamaConfig,
                       cos: jax.Array, sin: jax.Array) -> jax.Array:
    """QKV + RoPE + (ring|flash) attention + output proj. K/V stay in the
    narrow GQA layout; the flash path streams them natively and the
    sequence-parallel dispatch broadcasts them just-in-time. Shared by the
    dense block here and the MoE block (models/moe.py)."""
    b, s, _ = h.shape
    nh, hd = config.n_heads, config.head_dim
    q, k, v = qkv_proj(h, layer, config)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    q = constrain(q, ("batch", "heads", "seq", None))
    k = constrain(k, ("batch", "kv_heads", "seq", None))
    v = constrain(v, ("batch", "kv_heads", "seq", None))
    attn = _attention_dispatch(q, k, v, config)
    attn = attn.transpose(0, 2, 1, 3).reshape(b, s, nh * hd)
    return checkpoint_name(jnp.einsum("bsh,hd->bsd", attn, layer["wo"]),
                           "attn_proj")


def _block(config: LlamaConfig, cos, sin, x, layer: Params):
    h = rms_norm(x, layer["attn_norm"], config.norm_eps)
    x = x + attention_sublayer(h, layer, config, cos, sin)
    x = constrain(x, ("batch", "seq", None))

    h = rms_norm(x, layer["mlp_norm"], config.norm_eps)
    gate = checkpoint_name(
        jnp.einsum("bsd,df->bsf", h, layer["w_gate"]), "mlp_gate")
    up = jnp.einsum("bsd,df->bsf", h, layer["w_up"])
    # inlined swiglu_mlp so the mid-activation sharding constraint can sit
    # between the einsums (generate.py's decode uses the helper directly)
    ff = jax.nn.silu(gate) * up
    ff = constrain(ff, ("batch", "seq", "mlp"))
    x = x + jnp.einsum("bsf,fd->bsd", ff, layer["w_down"])
    return constrain(x, ("batch", "seq", None))


def embed_lookup(embed: jax.Array, tokens: jax.Array,
                 config: LlamaConfig) -> jax.Array:
    """Sharding-aware embedding lookup: (V, D) table x (B, S) ids ->
    (B, S, D) in the compute dtype.

    The table is stored ("vocab","embed") = (tp, fsdp); gathering from it
    directly makes the SPMD partitioner inherit the operand's embed-dim
    sharding on the output, and resharding THAT to ("batch","seq",None)
    triggers XLA's "Involuntary full rematerialization" fallback (the
    warning the multichip dryrun's dense leg printed). Constraining the
    ids to the batch layout and un-sharding the table's embed dim first (the
    standard FSDP weight all-gather) flips the partitioner to its
    masked-local-gather + all-reduce(tp) path: no replication, and the
    collectives are the same shapes FSDP pays for every weight."""
    tokens = constrain(tokens, ("batch", "seq"))
    table = constrain(embed, ("vocab", None))
    x = jnp.take(table, tokens, axis=0).astype(config.dtype)
    return constrain(x, ("batch", "seq", None))


def llama_hidden(params: Params, tokens: jax.Array,
                 config: LlamaConfig) -> jax.Array:
    """tokens: (B, S) int32 -> final-normed hidden states (B, S, dim)."""
    s = tokens.shape[1]
    cos, sin = rope_tables(config, s)
    x = embed_lookup(params["embed"], tokens, config)

    block = partial(_block, config, cos, sin)
    if config.remat:
        block = jax.checkpoint(block, policy=config.checkpoint_policy())

    def scan_body(x, layer):
        return block(x, layer), None

    x, _ = lax.scan(scan_body, x, params["layers"])
    return rms_norm(x, params["final_norm"], config.norm_eps)


def llama_forward(params: Params, tokens: jax.Array,
                  config: LlamaConfig) -> jax.Array:
    """tokens: (B, S) int32 -> logits (B, S, vocab) in f32."""
    x = llama_hidden(params, tokens, config)
    # bf16 operands, f32 accumulation: the MXU accumulates in f32 anyway,
    # so this matches an f32-cast matmul at the accumulator while running
    # at bf16 speed (the f32 cast halved MXU throughput for ~6% of model
    # FLOPs at llama3_1b_proxy scale).
    logits = jnp.einsum("bsd,dv->bsv", x, params["output"],
                        preferred_element_type=jnp.float32)
    return constrain(logits, ("batch", "seq", "vocab"))


def _head_loss(x: jax.Array, params: Params, targets: jax.Array,
               config: LlamaConfig) -> jax.Array:
    """LM-head + mean CE on final hidden states; fused-chunked when the
    config asks for it (never materializes full (B,S,V) logits)."""
    if config.xent_chunk > 0:
        from tony_tpu.ops.xent import fused_cross_entropy
        return fused_cross_entropy(x, params["output"], targets,
                                   chunk=config.xent_chunk)
    logits = jnp.einsum("bsd,dv->bsv", x, params["output"],
                        preferred_element_type=jnp.float32)
    logits = constrain(logits, ("batch", "seq", "vocab"))
    return cross_entropy(logits, targets)


def llama_pipeline_param_axes(config: LlamaConfig) -> Params:
    """Logical axes for the STAGED layer tree ((pp, L/pp, ...) layout):
    leading dim on the `pp` mesh axis, inner dims keeping the tensor/FSDP
    layout — stage weights shard on pp x fsdp x tp simultaneously."""
    # ("layers", ...) -> ("stage", "layers", ...): (L,...) reshaped to
    # (pp, L/pp, ...) keeps a per-stage layers dim after the stage dim
    return {k: ("stage",) + tuple(v)
            for k, v in llama_param_axes(config)["layers"].items()}


def llama_hidden_pipelined(params: Params, tokens: jax.Array,
                           config: LlamaConfig, mesh, n_micro: int,
                           n_virtual: int = 1) -> jax.Array:
    """Pipeline-parallel backbone up to the final norm (head applied by the
    caller, so the loss path can use the fused chunked CE).

    The L layers are split into pp stages
    (mesh's pp axis size), microbatches flow through the fill/drain
    schedule with a 1F1B-ordered hand-written backward
    (parallel/pipeline.py); embedding + head run outside the pipeline
    under the mesh's usual tp/fsdp rules. The pipeline's shard_map is
    manual over pp ONLY, so each stage's weights and activations keep
    their within-stage fsdp/tp sharding (VERDICT r2 item 2 — pp composes
    with tp/fsdp). Requires n_layers % pp == 0 and batch % n_micro == 0."""
    from jax.sharding import PartitionSpec as P

    from tony_tpu.ops.vma import varying_full
    from tony_tpu.parallel.pipeline import make_pipelined_fn

    pp = dict(mesh.shape).get("pp", 1)
    sp = dict(mesh.shape).get("sp", 1)
    L = config.n_layers
    if L % (pp * n_virtual) != 0:
        raise ValueError(f"n_layers {L} not divisible by "
                         f"pp*n_virtual={pp}*{n_virtual}")

    def stage_fn(stage_layers, x):
        # rope tables are computed (cheaply) INSIDE the stage so they are
        # fresh constants of the manual region; varying_full marks them +
        # the replicated-over-sp stage weights varying, and the pcast's
        # vjp is exactly the psum that reduces their cotangents over sp
        seq = x.shape[1] * sp if sp > 1 else x.shape[1]
        cos, sin = rope_tables(config, seq)
        if sp > 1:
            # each rank holds its local seq chunk: slice its rope rows
            idx = lax.axis_index("sp")
            cos = lax.dynamic_slice_in_dim(cos, idx * x.shape[1],
                                           x.shape[1], axis=0)
            sin = lax.dynamic_slice_in_dim(sin, idx * x.shape[1],
                                           x.shape[1], axis=0)
        cos, sin = varying_full(cos), varying_full(sin)
        stage_layers = jax.tree.map(varying_full, stage_layers)
        # pin the weights' Auto-axis layout INSIDE the manual region:
        # with dp in the mesh the partitioner otherwise invents leading-
        # dim shardings for the local stage stacks and pays involuntary
        # rematerializations re-sharding them (16-device dryrun, dp=2).
        # staged_axes[k][1:] = the per-chunk logical dims; manual axes
        # (pp/sp) are dropped by constrain automatically
        stage_layers = {k: constrain(p, staged_axes[k][1:])
                        for k, p in stage_layers.items()}
        block = partial(_block, config, cos, sin)
        if config.remat:
            block = jax.checkpoint(block, policy=config.checkpoint_policy())
        x, _ = lax.scan(lambda x, layer: (block(x, layer), None),
                        x, stage_layers)
        return x

    # (L, ...) -> (pp*v, L/(pp*v), ...): stage dim on pp, inner dims
    # fsdp/tp. For the interleaved schedule (v > 1) the chunks are laid
    # out so PartitionSpec('pp') hands device d its round-robin virtual
    # stages [d, pp+d, ...] (interleave_stage_dim)
    from tony_tpu.parallel.pipeline import interleave_stage_dim
    n_chunks = pp * n_virtual
    staged_axes = llama_pipeline_param_axes(config)
    staged_layers = {}
    for k, p in params["layers"].items():
        stacked = p.reshape((n_chunks, L // n_chunks) + p.shape[1:])
        if n_virtual > 1:
            # the contiguous-pp -> round-robin reorder is an all-to-all
            # GSPMD cannot plan through reshape/transpose (it falls back
            # to involuntary replication): make it explicit — all-gather
            # the stage dim (inner dims stay fsdp/tp-sharded, so the
            # payload is the already-sharded stack), reorder locally,
            # re-slice onto pp
            stacked = constrain(stacked,
                                (None, None) + tuple(staged_axes[k][2:]))
            stacked = interleave_stage_dim(stacked, pp, n_virtual)
        staged_layers[k] = constrain(stacked, staged_axes[k])

    x = embed_lookup(params["embed"], tokens, config)
    # with a real sp axis the pipeline's manual region widens to {pp, sp}
    # and microbatches enter sequence-sharded, so the stage can run
    # ring/ulysses attention directly (shard_map cannot nest)
    extra = ("sp",) if sp > 1 else ()
    mb_spec = P(None, None, "sp") if sp > 1 else P()
    pipe = make_pipelined_fn(stage_fn, mesh, n_micro=n_micro,
                             extra_manual=extra, mb_spec=mb_spec,
                             n_virtual=n_virtual)
    x = pipe(staged_layers, x)
    return rms_norm(x, params["final_norm"], config.norm_eps)


def llama_forward_pipelined(params: Params, tokens: jax.Array,
                            config: LlamaConfig, mesh, n_micro: int,
                            n_virtual: int = 1) -> jax.Array:
    """Pipelined forward -> logits (B, S, vocab) f32 (parity surface for
    tests; training uses llama_loss_pipelined which skips full logits when
    config.xent_chunk is set)."""
    x = llama_hidden_pipelined(params, tokens, config, mesh, n_micro,
                               n_virtual=n_virtual)
    return jnp.einsum("bsd,dv->bsv", x, params["output"],
                      preferred_element_type=jnp.float32)


def llama_loss_pipelined(params: Params, batch: dict[str, jax.Array],
                         config: LlamaConfig, mesh, n_micro: int,
                         n_virtual: int = 1) -> jax.Array:
    inputs, targets = unpack_lm_batch(batch)
    x = llama_hidden_pipelined(params, inputs, config, mesh, n_micro,
                               n_virtual=n_virtual)
    return _head_loss(x, params, targets, config)


def unpack_lm_batch(batch: dict[str, jax.Array]
                    ) -> tuple[jax.Array, jax.Array]:
    """{'tokens': (B,S+1)} or {'inputs','targets'} -> (inputs, targets)."""
    if "tokens" in batch:
        return batch["tokens"][:, :-1], batch["tokens"][:, 1:]
    return batch["inputs"], batch["targets"]


def cross_entropy(logits: jax.Array, targets: jax.Array) -> jax.Array:
    """Mean next-token CE; shared by the dense and MoE models."""
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


def llama_loss(params: Params, batch: dict[str, jax.Array],
               config: LlamaConfig) -> jax.Array:
    """Next-token cross entropy. batch: {'tokens': (B, S+1)} or
    {'inputs': (B,S), 'targets': (B,S)}."""
    inputs, targets = unpack_lm_batch(batch)
    x = llama_hidden(params, inputs, config)
    return _head_loss(x, params, targets, config)
