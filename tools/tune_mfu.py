"""MFU tuning harness: time llama3_1b_proxy train-step variants on the
live chip and print one JSON line per variant.

Usage: python tools/tune_mfu.py [variant ...]   (default: all)

Variants explore the single-chip levers (VERDICT r2 item 1): batch size,
remat on/off/policy, sequence length. Each runs in-process sequentially —
a chip belongs to one process at a time, so never run this alongside
another TPU job.
"""

from __future__ import annotations

import json
import sys
import time
from functools import partial

import jax
import jax.numpy as jnp
import optax

sys.path.insert(0, ".")
from tony_tpu.models.llama import get_config, llama_init, llama_loss  # noqa: E402
# the ONE peak-FLOPs table + MFU formula, shared with bench.py and the
# trainer's goodput metrics (observability/perf.py)
from tony_tpu.observability.perf import mfu_pct  # noqa: E402
from tony_tpu.train.step import make_train_step  # noqa: E402

# Measured on v5e (2026-07-30): base_b4 (save_flash remat) 67.8%,
# fullremat_b4 65.5%, b2 66.2%, b8 flat, noremat_*/dots_b4 exceed HBM
# (the remote-compile helper then 500s — that error usually means OOM).
VARIANTS: dict[str, dict] = {
    "base_b4":   dict(batch=4, seq=4096),
    "fullremat_b4": dict(batch=4, seq=4096, remat_policy="full"),
    "b8":        dict(batch=8, seq=4096),
    "b2":        dict(batch=2, seq=4096),
    # oom_v5e: tools/aot_rank.py compiled these against a detached v5e
    # topology — 19.64G / 31.31G / 18.18G (unfused_b8) vs 15.75G HBM —
    # so the default sweep skips them instead of burning a ~150s live
    # compile to rediscover the OOM (pass --all to force)
    "noremat_b2": dict(batch=2, seq=4096, remat=False, oom_v5e=True),
    "noremat_b4": dict(batch=4, seq=4096, remat=False, oom_v5e=True),
    "dots_b4":   dict(batch=4, seq=4096, policy="dots_with_no_batch_dims_saveable"),
    "seq8k_b2":  dict(batch=2, seq=8192),
    # fused chunked LM-head CE A/B (preset default is xent_chunk=1024;
    # 0 = full-logits path) — the lever that freed ~4 GB for b8
    "unfused_b4": dict(batch=4, seq=4096, xent_chunk=0),
    "unfused_b8": dict(batch=8, seq=4096, xent_chunk=0, oom_v5e=True),
    "xc512_b8":  dict(batch=8, seq=4096, xent_chunk=512),
    "xc2048_b8": dict(batch=8, seq=4096, xent_chunk=2048),
    # flash-kernel tile sweep (DEFAULT_BLOCK_Q/K = 512 measured 2.05x over
    # 128 on v5e; 1024 and 256 untried on the current kernel stack)
    "blk1024_b4": dict(batch=4, seq=4096, flash_block=1024),
    "blk256_b4": dict(batch=4, seq=4096, flash_block=256),
    "blkq1024k512_b4": dict(batch=4, seq=4096, flash_block_q=1024,
                            flash_block_k=512),
    # batch/seq grid corners never measured on-chip
    "b6":        dict(batch=6, seq=4096),
    "seq8k_b4":  dict(batch=4, seq=8192),
    "seq2k_b8":  dict(batch=8, seq=2048),
    # 8B-geometry single layer (bench's llama3_8b_layer metric, 63.04%
    # at r4's b1/blk512) — can a bigger batch or tile lift it?
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
    """Resolve a variant spec's preset + config overrides (shared with
    tools/aot_rank.py's offline cost-model ranking)."""
    overrides = {}
    if not spec.get("remat", True):
        overrides["remat"] = False
    if "remat_policy" in spec:
        overrides["remat_policy"] = spec["remat_policy"]
    if "xent_chunk" in spec:
        overrides["xent_chunk"] = spec["xent_chunk"]
    if spec.get("model") == "8b_layer":
        # mirror bench._bench_8b_layer's geometry: one 8B layer, small
        # vocab so embed/head don't dominate
        return get_config("llama3_8b", n_layers=1, vocab_size=8192,
                          max_seq=spec["seq"], **overrides)
    return get_config("llama3_1b_proxy", max_seq=spec["seq"], **overrides)


class variant_globals:
    """Context manager applying a spec's module-global knobs (flash
    block sizes, checkpoint policy) and restoring them on exit — the
    fallible setup shared by the live tuner and the AOT ranker."""

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


def run(name: str, spec: dict) -> dict:
    config = build_config(spec)
    # all fallible per-variant setup (policy lookup included) runs inside
    # the try so one bad variant reports its error line, and the with
    # block restores every global for the next variant
    try:
        with variant_globals(spec):
            params = llama_init(config, jax.random.PRNGKey(0))
            optimizer = optax.adamw(3e-4)
            step = make_train_step(partial(llama_loss, config=config),
                                   optimizer)
            opt_state = jax.jit(optimizer.init)(params)
            b, s = spec["batch"], spec["seq"]
            tokens = jax.random.randint(jax.random.PRNGKey(1), (b, s), 0,
                                        config.vocab_size, jnp.int32)
            batch = {"inputs": tokens,
                     "targets": jnp.roll(tokens, -1, axis=1)}
            for _ in range(2):
                params, opt_state, loss = step(params, opt_state, batch)
            float(loss)
            t0 = time.monotonic()
            n = 6
            for _ in range(n):
                params, opt_state, loss = step(params, opt_state, batch)
            float(loss)
            dt = (time.monotonic() - t0) / n
            tok_s = b * s / dt
            mfu = mfu_pct(tok_s, config.flops_per_token(s),
                          jax.devices()[0])
            return {"variant": name, "step_s": round(dt, 4),
                    "tok_s": round(tok_s, 1), "mfu_pct": round(mfu, 2)}
    except Exception as e:  # noqa: BLE001 — report and move on (e.g. OOM)
        return {"variant": name,
                "error": f"{type(e).__name__}: {str(e)[:200]}"}


def main() -> None:
    argv = [a for a in sys.argv[1:] if a != "--all"]
    force_all = "--all" in sys.argv[1:]
    names = argv or list(VARIANTS)
    for name in names:
        spec = VARIANTS[name]
        if spec.get("oom_v5e") and not force_all and not argv:
            print(json.dumps({"variant": name,
                              "skipped": "oom_v5e (aot_rank verdict)"}),
                  flush=True)
            continue
        print(json.dumps(run(name, spec)), flush=True)


if __name__ == "__main__":
    main()
