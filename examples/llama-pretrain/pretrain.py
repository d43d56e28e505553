"""Llama pretrain — the BASELINE.json north-star workload.

Reference target: "Llama-3 8B JAX/Flax pretrain via new JAXRuntime
(v5p-32, tony.worker.tpus=4)". The orchestrator gang-schedules the worker
processes, renders the JAX coordinator + TPU_MESH_* env, and this script
brings up the mesh (fsdp/tp/sp per conf), shards the params with the
model's logical axes, and trains with checkpoint/resume — surviving AM
retries via the checkpoint dir (ATTEMPT_NUMBER advances, state resumes).

Submit (v5p-32 shape):
  python -m tony_tpu.cli submit --executes examples/llama-pretrain/pretrain.py \
      --task_params "--config llama3_8b --steps 1000" \
      --conf tony.worker.instances=4 --conf tony.worker.tpus=4 \
      --conf tony.tpu.mesh-shape=4,4 --conf tony.tpu.mesh-axes=fsdp,tp
"""

import argparse
import logging
import os
import sys
from functools import partial

sys.path.insert(0, os.environ.get("TONY_REPO_ROOT",
                                  os.path.join(os.path.dirname(__file__),
                                               "..", "..")))

from tony_tpu.models.llama import (  # noqa: E402
    get_config, llama_init, llama_loss, llama_param_axes,
)
from tony_tpu.train.data import synthetic_tokens  # noqa: E402
from tony_tpu.train.trainer import Trainer, TrainerConfig  # noqa: E402


def _eval_stream(args, seq, config, process_index):
    """Held-out eval batches from the SAME source as training: the real
    corpus (disjoint sampling seed) when --data is given, else the
    synthetic stream with a disjoint seed."""
    if args.data:
        from tony_tpu.train.native_data import token_batches
        return token_batches(args.data, args.batch_size, seq,
                             seed=1_000_000 + process_index)
    return synthetic_tokens(args.batch_size, seq, config.vocab_size,
                            seed=args.seed + 1,
                            process_index=process_index)


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--config", default="tiny",
                        help="preset: tiny|bench_350m|llama3_1b_proxy|"
                             "llama3_8b|llama3_70b, or a MoE preset "
                             "(moe_tiny|mixtral_proxy)")
    parser.add_argument("--steps", type=int, default=100)
    parser.add_argument("--log-every", type=int, default=10,
                        help="steps between logged losses")
    parser.add_argument("--seed", type=int, default=0,
                        help="seed of the initial weights and of the "
                             "synthetic token stream")
    parser.add_argument("--batch-size", type=int, default=8)
    parser.add_argument("--seq-len", type=int, default=0,
                        help="0 = the preset's max_seq")
    parser.add_argument("--n-layers", type=int, default=0,
                        help="override the preset's layer count (0 = "
                             "preset; pipelining needs n_layers %% "
                             "(pp*virtual) == 0)")
    parser.add_argument("--grad-accum", type=int, default=1,
                        help="microbatch gradient-accumulation steps")
    parser.add_argument("--eval-every", type=int, default=0,
                        help="held-out eval cadence in steps (0 = off)")
    parser.add_argument("--master-weights", action="store_true",
                        help="f32 master copy for bf16 params")
    parser.add_argument("--checkpoint-dir", default="")
    parser.add_argument("--checkpoint-every", type=int, default=0)
    parser.add_argument("--data", default="",
                        help="raw int32 token shard; synthetic when empty")
    parser.add_argument("--pp-micro", type=int, default=0,
                        help="pipeline microbatches; >0 with a pp axis in "
                             "tony.tpu.mesh-axes selects the pipelined "
                             "loss (parallel/pipeline.py)")
    parser.add_argument("--pp-virtual", type=int, default=1,
                        help="virtual chunks per pipeline stage (>1 = "
                             "interleaved schedule, bubble/(v))")
    args = parser.parse_args()

    logging.basicConfig(level=logging.INFO)
    # device evidence is logged by Trainer.setup() AFTER distributed
    # init — touching jax.devices() here would initialize the local
    # backend and break jax.distributed.initialize() on multi-worker runs
    overrides = {"n_layers": args.n_layers} if args.n_layers else {}
    from tony_tpu.models.moe import is_moe_preset
    is_moe = is_moe_preset(args.config)
    if is_moe:
        from tony_tpu.models.moe import (
            get_moe_config, moe_init, moe_loss, moe_param_axes,
        )
        config = get_moe_config(args.config, **overrides)
        init_fn = partial(moe_init, config)
        base_loss = partial(moe_loss, config=config)
        param_axes = moe_param_axes(config)
    else:
        config = get_config(args.config, **overrides)
        init_fn = partial(llama_init, config)
        base_loss = partial(llama_loss, config=config)
        param_axes = llama_param_axes(config)
    seq = args.seq_len or config.max_seq
    process_index = int(os.environ.get("JAX_PROCESS_ID", "0"))

    def clipped_tokens():
        if args.data:
            # native prefetching mmap loader (falls back to numpy)
            from tony_tpu.train.native_data import token_batches
            yield from token_batches(args.data, args.batch_size, seq,
                                     seed=process_index)
        else:
            yield from synthetic_tokens(args.batch_size, seq,
                                        config.vocab_size, seed=args.seed,
                                        process_index=process_index)

    # pipelined loss when requested and the orchestrator rendered a pp
    # axis (tony.tpu.mesh-axes=pp,...): the 1F1B schedule, interleaved
    # when --pp-virtual > 1; the trainer binds the runtime mesh at setup
    mesh_axes = [a.strip() for a in
                 os.environ.get("TPU_MESH_AXES", "").split(",")]
    pipelined = args.pp_micro > 0 and "pp" in mesh_axes
    if pipelined:
        if is_moe:
            raise SystemExit("pipelined training is the dense-Llama "
                             "path; MoE scales via the ep/fsdp axes")
        from tony_tpu.models.llama import llama_loss_pipelined
        loss_fn = partial(llama_loss_pipelined, config=config,
                          n_micro=args.pp_micro,
                          n_virtual=args.pp_virtual)
    else:
        if args.pp_micro > 0:
            logging.warning(
                "--pp-micro %d requested but tony.tpu.mesh-axes (%s) has "
                "no pp axis — training WITHOUT pipeline parallelism",
                args.pp_micro, os.environ.get("TPU_MESH_AXES", ""))
        loss_fn = base_loss

    trainer = Trainer(
        loss_fn=loss_fn,
        loss_takes_mesh=pipelined,
        init_fn=init_fn,
        data_iter=clipped_tokens(),
        config=TrainerConfig(
            num_steps=args.steps, log_every=args.log_every,
            seed=args.seed,
            checkpoint_dir=args.checkpoint_dir,
            checkpoint_every=args.checkpoint_every,
            grad_accum=args.grad_accum,
            eval_every=args.eval_every,
            master_weights=args.master_weights,
            # MFU/goodput accounting (observability/perf.py), on the
            # benchmark's scale; MoE configs count ACTIVE matmul params
            flops_per_token=config.flops_per_token(seq)),
        param_axes=param_axes,
        eval_data_iter=(_eval_stream(args, seq, config, process_index)
                        if args.eval_every else None),
    )
    final_loss = trainer.run()
    print(f"final loss {final_loss:.4f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
