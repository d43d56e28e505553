"""Offline screening of train-step variants with the real XLA:TPU compiler.

JAX's AOT path runs the REAL XLA:TPU compiler against a detached
TopologyDescription — no chip needed. So without spending chip time, every
variant below can be compiled for an actual v5e target and
screened by its compiled HBM plan (argument + temp bytes vs the 16 GiB
chip) and a roofline bound (model-accounted FLOPs vs MXU peak, XLA
'bytes accessed' vs HBM bandwidth).

This is SCREENING, not measurement: XLA's cost_analysis can't price the
Mosaic custom-call kernels (its optimal_seconds comes back as a negative
sentinel on these programs, and its flops/bytes skip kernel internals),
so the bound is a floor on step time, not an estimate. The value is
(a) variants that will OOM or blow compile are eliminated offline, and
(b) the HBM plan per variant is known — so chip time is spent
measuring only configs that can actually run. (The plan summed here,
memory_analysis()'s arguments + temporaries, charges a layer scan's saved
stacks twice; the chip obeys the compile's buffer assignment, which reads
2-4 GiB lower for a train step: PERF.md section 7, PR 47. The recorded
verdicts in aot_rank_result*.json are by the sum.)

Usage (CPU host, no TPU):
  JAX_PLATFORMS=cpu python tools/aot_rank.py [variant ...]

One JSON line per variant, then a ranked summary on stderr; full results
in tools/aot_rank_result.json.
"""

from __future__ import annotations

import json
import os
import sys
import time
from functools import partial

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import optax  # noqa: E402

from tony_tpu.models.llama import (  # noqa: E402
    get_config, llama_init, llama_loss,
)
from tony_tpu.observability.perf import peak_flops  # noqa: E402
from tony_tpu.train.step import make_train_step  # noqa: E402

V5E_HBM = 16 * 1024 ** 3
RESULT_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "aot_rank_result.json")

# The single-chip levers of the llama3_1b_proxy train step: batch size,
# remat on/off/policy, sequence length, fused cross-entropy chunk, flash
# tile. What each costs in time is not measured (PERF.md).
VARIANTS: dict[str, dict] = {
    "base_b4":   dict(batch=4, seq=4096),
    "fullremat_b4": dict(batch=4, seq=4096, remat_policy="full"),
    "b8":        dict(batch=8, seq=4096),
    "b2":        dict(batch=2, seq=4096),
    # this tool's own verdict on noremat_b2, noremat_b4 and unfused_b8:
    # 19.64G / 31.31G / 18.18G against 15.75G of HBM
    "noremat_b2": dict(batch=2, seq=4096, remat=False),
    "noremat_b4": dict(batch=4, seq=4096, remat=False),
    "dots_b4":   dict(batch=4, seq=4096, policy="dots_with_no_batch_dims_saveable"),
    "seq8k_b2":  dict(batch=2, seq=8192),
    # fused chunked LM-head CE (preset default is xent_chunk=1024;
    # 0 = full-logits path) — the lever that freed ~4 GB for b8
    "unfused_b4": dict(batch=4, seq=4096, xent_chunk=0),
    "unfused_b8": dict(batch=8, seq=4096, xent_chunk=0),
    "xc512_b8":  dict(batch=8, seq=4096, xent_chunk=512),
    "xc2048_b8": dict(batch=8, seq=4096, xent_chunk=2048),
    # flash-kernel tiles beside DEFAULT_BLOCK_Q/K = 512
    "blk1024_b4": dict(batch=4, seq=4096, flash_block=1024),
    "blk256_b4": dict(batch=4, seq=4096, flash_block=256),
    "blkq1024k512_b4": dict(batch=4, seq=4096, flash_block_q=1024,
                            flash_block_k=512),
    "b6":        dict(batch=6, seq=4096),
    "seq8k_b4":  dict(batch=4, seq=8192),
    "seq2k_b8":  dict(batch=8, seq=2048),
    # one layer of the 8B geometry, small vocab so embed/head don't
    # dominate
    "L8b_b1":    dict(model="8b_layer", batch=1, seq=4096),
    "L8b_b2":    dict(model="8b_layer", batch=2, seq=4096),
    "L8b_b4":    dict(model="8b_layer", batch=4, seq=4096),
    "L8b_blk1024_b2": dict(model="8b_layer", batch=2, seq=4096,
                           flash_block=1024),
    "L8b_noremat_b1": dict(model="8b_layer", batch=1, seq=4096,
                           remat=False),
    "L8b_noremat_b2": dict(model="8b_layer", batch=2, seq=4096,
                           remat=False),
}


def build_config(spec: dict):
    """Resolve a variant spec's preset + config overrides."""
    overrides = {}
    if not spec.get("remat", True):
        overrides["remat"] = False
    if "remat_policy" in spec:
        overrides["remat_policy"] = spec["remat_policy"]
    if "xent_chunk" in spec:
        overrides["xent_chunk"] = spec["xent_chunk"]
    if spec.get("model") == "8b_layer":
        return get_config("llama3_8b", n_layers=1, vocab_size=8192,
                          max_seq=spec["seq"], **overrides)
    return get_config("llama3_1b_proxy", max_seq=spec["seq"], **overrides)


class variant_globals:
    """Context manager applying a spec's module-global knobs (flash
    block sizes, checkpoint policy) and restoring them on exit."""

    def __init__(self, spec: dict):
        self.spec = spec

    def __enter__(self):
        import tony_tpu.models.llama as llama_mod
        import tony_tpu.ops.attention as attn_mod
        self._llama_mod, self._attn_mod = llama_mod, attn_mod
        self._real_ckpt = None
        self._saved_blocks = (attn_mod.DEFAULT_BLOCK_Q,
                              attn_mod.DEFAULT_BLOCK_K)
        policy = self.spec.get("policy")
        if policy is not None:
            pol = getattr(jax.checkpoint_policies, policy)
            self._real_ckpt = jax.checkpoint
            llama_mod.jax.checkpoint = partial(self._real_ckpt,
                                               policy=pol)
        attn_mod.DEFAULT_BLOCK_Q = self.spec.get(
            "flash_block_q",
            self.spec.get("flash_block", self._saved_blocks[0]))
        attn_mod.DEFAULT_BLOCK_K = self.spec.get(
            "flash_block_k",
            self.spec.get("flash_block", self._saved_blocks[1]))
        return self

    def __exit__(self, *exc):
        (self._attn_mod.DEFAULT_BLOCK_Q,
         self._attn_mod.DEFAULT_BLOCK_K) = self._saved_blocks
        if self._real_ckpt is not None:
            self._llama_mod.jax.checkpoint = self._real_ckpt
        return False


def _single_v5e_mesh():
    from jax.experimental import topologies

    # v5e:1x1 violates the default chips-per-host bound; take one device
    # of the smallest valid slice — the compiled program is single-chip
    topo = topologies.get_topology_desc("v5e:2x2", "tpu")
    return jax.sharding.Mesh([topo.devices[0]], ("chip",)), topo.devices[0]


def rank_one(name: str, spec: dict, mesh, dev) -> dict:
    from jax.sharding import NamedSharding, PartitionSpec as P

    config = build_config(spec)
    b, s = spec["batch"], spec["seq"]

    def sds(tree):
        return jax.tree.map(
            lambda l: jax.ShapeDtypeStruct(
                l.shape, l.dtype, sharding=NamedSharding(mesh, P())),
            tree)

    with variant_globals(spec):
        params_shape = jax.eval_shape(partial(llama_init, config),
                                      jax.random.PRNGKey(0))
        optimizer = optax.adamw(3e-4)
        opt_shape = jax.eval_shape(optimizer.init, params_shape)
        step = make_train_step(partial(llama_loss, config=config),
                               optimizer)
        tok = jax.ShapeDtypeStruct((b, s), jnp.int32,
                                   sharding=NamedSharding(mesh, P()))
        t0 = time.monotonic()
        exe = jax.jit(step).lower(
            sds(params_shape), sds(opt_shape),
            {"inputs": tok, "targets": tok}).compile()
    ca = exe.cost_analysis()
    ma = exe.memory_analysis()
    live = (ma.argument_size_in_bytes + ma.temp_size_in_bytes)
    out = {
        "variant": name,
        "hbm_gib": round(live / 1024 ** 3, 2),
        "hbm_temp_gib": round(ma.temp_size_in_bytes / 1024 ** 3, 2),
        "fits_v5e": bool(live <= V5E_HBM),
        "compile_s": round(time.monotonic() - t0, 1),
    }
    # roofline FLOOR on step time: model-accounted train FLOPs at MXU
    # peak vs XLA-visible HBM traffic at ~819 GB/s (v5e). A real step is
    # slower than both; the bound mainly exposes bandwidth-heavy configs.
    model_flops = b * s * config.flops_per_token(s)
    t_compute = model_flops / peak_flops(dev)
    t_bw = float(ca.get("bytes accessed", 0.0)) / 819e9
    floor_s = max(t_compute, t_bw)
    out["floor_ms"] = round(floor_s * 1e3, 2)
    out["bound"] = "bandwidth" if t_bw > t_compute else "compute"
    out["mfu_ceiling_pct"] = round(100.0 * t_compute / floor_s, 2)
    return out


def rank_decode(mesh) -> list[dict]:
    """AOT A/B of the decode step: bf16 vs int8 weight-only vs int8
    weights + int8 KV cache, against the real v5e target. The verdict
    that matters is memory_analysis: temp==0 proves the dequant FUSES
    (a single materialized bf16 LM head alone would be ~131 MB of temp),
    and argument bytes are the per-step weight/cache stream. Measured
    2026-07-31: bf16 2376.3 MB args / int8 1305.9 MB, both temp 0."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tony_tpu.models.generate import decode_step
    from tony_tpu.models.llama import get_config, llama_init
    from tony_tpu.models.quant import quantize_params

    config = get_config("llama3_1b_proxy")
    b, cache_len = 8, 192
    nl, nkv, hd = config.n_layers, config.n_kv_heads, config.head_dim

    def sds_tree(tree):
        return jax.tree.map(
            lambda l: jax.ShapeDtypeStruct(
                l.shape, l.dtype, sharding=NamedSharding(mesh, P())),
            tree)

    params_s = jax.eval_shape(partial(llama_init, config),
                              jax.random.PRNGKey(0))
    qparams_s = jax.eval_shape(quantize_params, params_s)
    tok = jax.ShapeDtypeStruct((b,), jnp.int32,
                               sharding=NamedSharding(mesh, P()))
    pos = jax.ShapeDtypeStruct((), jnp.int32,
                               sharding=NamedSharding(mesh, P()))

    def cache_sds(qc):
        kv = jnp.int8 if qc else jnp.bfloat16
        c = {"k": jax.ShapeDtypeStruct((nl, b, nkv, cache_len, hd), kv),
             "v": jax.ShapeDtypeStruct((nl, b, nkv, cache_len, hd), kv)}
        if qc:
            c["k_scale"] = jax.ShapeDtypeStruct(
                (nl, b, nkv, cache_len, 1), jnp.float32)
            c["v_scale"] = jax.ShapeDtypeStruct(
                (nl, b, nkv, cache_len, 1), jnp.float32)
        return sds_tree(c)

    results = []
    for tag, ps, qc in (("decode_bf16", params_s, False),
                        ("decode_int8", qparams_s, False),
                        ("decode_int8_qcache", qparams_s, True)):
        t0 = time.monotonic()
        exe = jax.jit(partial(decode_step, config=config)).lower(
            sds_tree(ps), cache=cache_sds(qc), token=tok,
            pos=pos).compile()
        ma = exe.memory_analysis()
        rec = {"variant": tag,
               "args_mb": round(ma.argument_size_in_bytes / 1e6, 1),
               "temp_mb": round(ma.temp_size_in_bytes / 1e6, 1),
               "dequant_fused": bool(ma.temp_size_in_bytes < 16e6),
               "compile_s": round(time.monotonic() - t0, 1)}
        results.append(rec)
        print(json.dumps(rec), flush=True)
    return results


def rank_decode_8b(mesh) -> list[dict]:
    """The capability-unlock check: Llama-3-8B single-chip v5e serving.
    bf16 CANNOT fit (16.07 GB params alone vs 15.75 GB HBM — the real
    compiler OOMs at 15.96G used), int8 weights + int8 KV cache FITS
    (9.12 GB args, dequant fused, temp 0) at batch 4 x 2k context.
    Measured 2026-07-31 via this mode (--decode-8b)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tony_tpu.models.generate import decode_step
    from tony_tpu.models.llama import get_config, llama_init
    from tony_tpu.models.quant import quantize_params

    # TONY_AOT_8B_CTX extends the check to long contexts (verified
    # 2026-07-31: 32k-ctx b1 int8+qcache fits at 10.78 GB, temp 0.5 MB)
    cache_len = int(os.environ.get("TONY_AOT_8B_CTX", "2048"))
    b = 4 if cache_len <= 4096 else 1
    config = get_config("llama3_8b", max_seq=max(8192, cache_len))
    nl, nkv, hd = config.n_layers, config.n_kv_heads, config.head_dim

    def sds_tree(tree):
        return jax.tree.map(
            lambda l: jax.ShapeDtypeStruct(
                l.shape, l.dtype, sharding=NamedSharding(mesh, P())),
            tree)

    params_s = jax.eval_shape(partial(llama_init, config),
                              jax.random.PRNGKey(0))
    tok = jax.ShapeDtypeStruct((b,), jnp.int32,
                               sharding=NamedSharding(mesh, P()))
    pos = jax.ShapeDtypeStruct((), jnp.int32,
                               sharding=NamedSharding(mesh, P()))

    def cache_sds(qc):
        kv = jnp.int8 if qc else jnp.bfloat16
        c = {"k": jax.ShapeDtypeStruct((nl, b, nkv, cache_len, hd), kv),
             "v": jax.ShapeDtypeStruct((nl, b, nkv, cache_len, hd), kv)}
        if qc:
            c["k_scale"] = jax.ShapeDtypeStruct(
                (nl, b, nkv, cache_len, 1), jnp.float32)
            c["v_scale"] = jax.ShapeDtypeStruct(
                (nl, b, nkv, cache_len, 1), jnp.float32)
        return sds_tree(c)

    results = []
    for tag, ps, qc in (
            ("8b_decode_bf16", params_s, False),
            ("8b_decode_int8_qcache",
             jax.eval_shape(quantize_params, params_s), True)):
        t0 = time.monotonic()
        try:
            exe = jax.jit(partial(decode_step, config=config)).lower(
                sds_tree(ps), cache=cache_sds(qc), token=tok,
                pos=pos).compile()
            ma = exe.memory_analysis()
            rec = {"variant": tag, "fits_v5e": True,
                   "args_gb": round(
                       ma.argument_size_in_bytes / 1e9, 2),
                   "temp_mb": round(ma.temp_size_in_bytes / 1e6, 1),
                   "compile_s": round(time.monotonic() - t0, 1)}
        except Exception as e:
            rec = {"variant": tag, "fits_v5e": False,
                   "error": f"{type(e).__name__}: {str(e)[:140]}"}
        results.append(rec)
        print(json.dumps(rec), flush=True)
    return results


def main() -> int:
    for flag, fn in (("--decode", rank_decode),
                     ("--decode-8b", rank_decode_8b)):
        if flag in sys.argv[1:]:
            mesh, _ = _single_v5e_mesh()
            results = fn(mesh)
            with open(RESULT_PATH.replace(
                    ".json", f"_{flag.strip('-').replace('-', '_')}.json"),
                    "w", encoding="utf-8") as f:
                json.dump({"measured_at": time.strftime(
                    "%Y-%m-%dT%H:%MZ", time.gmtime()),
                    "results": results}, f, indent=2)
            return 0
    names = sys.argv[1:] or list(VARIANTS)
    mesh, dev = _single_v5e_mesh()
    results = []
    for name in names:
        try:
            rec = rank_one(name, VARIANTS[name], mesh, dev)
        except Exception as e:  # rank what compiles; report the rest
            rec = {"variant": name,
                   "error": f"{type(e).__name__}: {str(e)[:160]}"}
        results.append(rec)
        print(json.dumps(rec), flush=True)
    ranked = sorted((r for r in results if "mfu_ceiling_pct" in r),
                    key=lambda r: (-r["fits_v5e"], -r["mfu_ceiling_pct"]))
    for i, r in enumerate(ranked):
        print(f"[rank {i + 1}] {r['variant']}: ceiling "
              f"{r['mfu_ceiling_pct']}% ({r['bound']}-bound, hbm "
              f"{r['hbm_gib']} GiB, fits={r['fits_v5e']})",
              file=sys.stderr)
    for r in results:
        if "error" in r:
            print(f"[fail] {r['variant']}: {r['error']}", file=sys.stderr)
    with open(RESULT_PATH, "w", encoding="utf-8") as f:
        json.dump({"measured_at": time.strftime(
            "%Y-%m-%dT%H:%MZ", time.gmtime()), "results": results},
            f, indent=2)
    return 0


if __name__ == "__main__":
    main()
