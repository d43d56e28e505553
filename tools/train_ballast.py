"""How much device memory a train step really takes: hold a ballast of
`--ballast-gib` on the chip, then build the step as the Trainer does
(adamw, bf16 state, donated; defaults: the train-4k cell's shapes,
benchmark/configs/mistral-7b-train.json) and run it twice. One JSON line;
exit 0 if both steps ran, 1 if the device ran out of memory. The largest
ballast that runs, b, gives the step's true peak as (usable - b); which of
the compiler's two counts that agrees with is in PERF.md §6 (PR 47).
One attempt a process (a failed step may have eaten its donated state):

  chiprun --chips 1 -- python tools/train_ballast.py --ballast-gib 3.5

`--save` tries another set of saved names than `save_flash`'s
(models/llama.py SAVE_FLASH_NAMES) without editing the policy.
"""

from __future__ import annotations

import argparse
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

from tony_tpu.models import llama  # noqa: E402
from tony_tpu.train.step import make_train_step  # noqa: E402
from tony_tpu.utils.compilecache import enable_compile_cache  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--ballast-gib", type=float, required=True)
    ap.add_argument("--layers", type=int, default=5)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seq", type=int, default=4096)
    ap.add_argument("--save", default="", help="comma-separated names")
    args = ap.parse_args()
    if args.save:
        llama.SAVE_FLASH_NAMES = tuple(args.save.split(","))
    config = llama.LlamaConfig(
        vocab_size=32000, dim=4096, n_layers=args.layers, n_heads=32,
        n_kv_heads=8, ffn_dim=14336, max_seq=args.seq, rope_theta=10000.0,
        xent_chunk=1024, remat=True, remat_policy="save_flash")
    enable_compile_cache(jax)
    out = {"ballast_gib": args.ballast_gib, "layers": args.layers,
           "saved": list(llama.SAVE_FLASH_NAMES),
           "device": jax.devices()[0].device_kind, "ran": False}
    try:
        ballast = jnp.zeros((int(args.ballast_gib * 2 ** 30),), jnp.uint8)
        params = llama.llama_init(config, jax.random.PRNGKey(0))
        optimizer = optax.adamw(optax.warmup_cosine_decay_schedule(
            0.0, 3e-4, 10, 100_000), weight_decay=0.01)
        opt_state = jax.jit(optimizer.init)(params)
        step = make_train_step(partial(llama.llama_loss, config=config),
                               optimizer)
        batch = {"tokens": jax.random.randint(       # as the cell's rows
            jax.random.PRNGKey(1), (args.batch, args.seq + 1), 0, 32000)}
        losses = []
        for _ in range(2):
            t0 = time.perf_counter()
            params, opt_state, loss = step(params, opt_state, batch)
            losses.append(float(loss))
        out.update(ran=True, losses=losses, ballast_bytes=ballast.nbytes,
                   second_step_ms=round(1e3 * (time.perf_counter() - t0), 1))
    except Exception as e:  # noqa: BLE001 — the verdict is the message
        out["error"] = f"{type(e).__name__}: {str(e)[:300]}"
    stats = jax.devices()[0].memory_stats() or {}
    out.update({k: stats[k] for k in ("peak_bytes_in_use", "bytes_limit")
                if k in stats})
    print(json.dumps(out), flush=True)
    return 0 if out["ran"] else 1


if __name__ == "__main__":
    sys.exit(main())
