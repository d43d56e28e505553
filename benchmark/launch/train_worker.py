"""The user process a training cell submits through client -> AM ->
executor: the program's `Trainer` (its mesh, sharding, optimizer, jitted
donated step and `PrefetchIterator` feed) at the cell's configuration, fed
by the benchmark's seeded token rows, and driven by its own `Trainer.run()`
throughout. The model's loss, initialiser and sharding come from the
`program.py` of the configuration's family (benchmark/families/). `run()`
trains to `config.num_steps` and may be called again with a larger one, so
one trainer runs, in turn: the first step (the optimizer's state then
gives the first gradient), the rest of the steps the reference follows
(they also tell the step's time), and the window, whose number of steps is
the seconds asked for over that time.

The trainer logs every step (`log_every` 1): at each step its loop waits
for the step before, so its step log (`metrics_history`: step, loss,
seconds since the loop began) stamps the end of every step but a run's
last. The window opens at the end of its run's first step and closes at
the end of its last but one: whole steps, each ended by the loop's own
wait on its loss, with the loop's log flush, metrics push, ledger and
profile hooks inside them. What the harness needs is written to
`<out>/worker_record.json`.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
from functools import partial

print("BENCH_START " + json.dumps({"t": time.monotonic()}), flush=True)

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))                    # benchmark/
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))   # checkout


def _leaf_names(tree) -> list:
    import jax
    return [jax.tree_util.keystr(p).replace("['", "").replace("']", ".")
            .rstrip(".") for p, _ in jax.tree_util.tree_leaves_with_path(tree)]


def _leaf_norms(tree) -> dict:
    import jax
    import jax.numpy as jnp
    norm = jax.jit(lambda x: jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))))
    return {n: float(norm(leaf)) for n, leaf in
            zip(_leaf_names(tree), jax.tree.leaves(tree))}


def _delta_norms(params, init, config, seed: int) -> dict:
    """Norm of (params - initial params), leaf by leaf. The initial leaf
    is made again from the seed by `init`, the program that made it the
    first time, so no second copy of the model is held. Two programs, not
    one: fused into the difference, the compiler may skip the initial
    leaf's rounding to bfloat16 (xla_allow_excess_precision), and the
    "change" would then be that rounding."""
    import jax
    import jax.numpy as jnp
    from lib import inproc
    diff = jax.jit(lambda x, y: jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32) - y.astype(jnp.float32)))))
    out = {}
    for name, leaf, (_, init) in zip(
            _leaf_names(params), jax.tree.leaves(params),
            inproc.seeded_leaves(init, config, seed)):
        out[name] = float(diff(leaf, init))
        init.delete()
    return out


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--config", required=True)
    p.add_argument("--traffic", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, default=0)
    p.add_argument("--out", required=True)
    p.add_argument("--sabotage", default="none", choices=("none", "noop"))
    args = p.parse_args()
    import logging
    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s: %(message)s")

    def mark(what: str) -> None:
        print(f"BENCH_MARK {what} {time.monotonic():.3f}", flush=True)

    with open(args.config, encoding="utf-8") as f:
        cfg = json.load(f)
    with open(args.traffic, encoding="utf-8") as f:
        mix = json.load(f)
    os.makedirs(args.out, exist_ok=True)

    import jax
    from lib import inproc, spec, traffic
    compile_log = inproc.install_compile_log()
    from tony_tpu.train.trainer import Trainer, TrainerConfig

    family = spec.load_family(args.config, cfg)
    model = family.program.training(cfg, args.seed)
    opt = cfg["run"]["optimizer"]
    trainer = Trainer(
        loss_fn=model["loss_fn"], init_fn=model["init_fn"],
        data_iter=traffic.train_batches(mix, cfg["vocab_size"], args.seed),
        config=TrainerConfig(
            num_steps=opt["schedule_steps"], log_every=1, seed=0,
            learning_rate=opt["learning_rate"],
            warmup_steps=opt["warmup_steps"],
            weight_decay=opt["weight_decay"]),
        param_axes=model["param_axes"])
    mark("imports_done")
    trainer.setup()
    mark("trainer_setup_done")
    if args.sabotage == "noop":
        # tests only: a step that returns its state unchanged
        loss_only = jax.jit(model["loss_fn"])
        trainer.train_step = lambda p, o, b: (p, o, loss_only(p, b))

    def run_to(step: int) -> tuple:
        """`Trainer.run()` to `step` steps in all: (when it was called,
        the step log it added)."""
        trainer.config.num_steps = step
        seen = len(trainer.metrics_history)
        t = time.monotonic()
        trainer.run()
        return t, trainer.metrics_history[seen:]

    def step_seconds(log: list) -> list:
        return [b["elapsed_s"] - a["elapsed_s"] for a, b in zip(log, log[1:])]

    record = {"seed": args.seed, "tokens_per_step":
              int(mix["batch_size"]) * int(mix["seq_len"])}
    # the first steps, which the reference follows
    t, log = run_to(1)
    record["compile_s"] = time.monotonic() - t
    first = [log[0]["loss"]]
    mark("first_step_done")
    mu = trainer.opt_state[0].mu
    b1 = opt["b1"]
    from lib import probe
    with jax.set_mesh(trainer.mesh):
        record["grad_norms"] = {k: v / (1.0 - b1)
                                for k, v in _leaf_norms(mu).items()}
        record["grad_proj"] = {
            n: [float(v) / (1.0 - b1) for v in probe.projections(x)]
            for n, x in zip(_leaf_names(mu), jax.tree.leaves(mu))}
    _, log = run_to(int(mix["check_steps"]))
    record["first_losses"] = first + [r["loss"] for r in log]
    step_s = step_seconds(log)[-1]     # sizes the window, no more
    mark("check_steps_done")
    with jax.set_mesh(trainer.mesh):
        record["delta_norms"] = _delta_norms(
            trainer.params, model["init"], model["config"], args.seed)
    mark("delta_norms_done")
    steps = max(1, int(-(-args.seconds // step_s)))
    mark(f"window_sized step_s={step_s:.4f} window_steps={steps}")

    def trace_some_steps(first_step: int) -> None:
        """From another thread, as only this process can trace its chip:
        once the window's run is `first_step` steps in, for the time of
        `trace_steps` steps."""
        while trainer.step < first_step:
            time.sleep(0.01)
        jax.profiler.start_trace(os.path.join(args.out, "trace"))
        time.sleep(int(mix["trace_steps"]) * step_s)
        jax.profiler.stop_trace()

    tracer = None
    if args.trace:
        tracer = threading.Thread(target=trace_some_steps,
                                  args=(trainer.step + 4,), daemon=True)
        tracer.start()
    # the window: one run of `steps` + 2 steps. Its step log stamps the
    # end of every step but the last; the window is the `steps` steps from
    # the end of the first (before which run() lowers the step again to
    # log its kernels) to the end of the last but one.
    stall0 = trainer.ledger.snapshot()["phases"].get("input_stall", 0.0)
    t_run, log = run_to(trainer.step + steps + 2)
    stall1 = trainer.ledger.snapshot()["phases"].get("input_stall", 0.0)
    if tracer is not None:
        tracer.join()
    # run() reads its own clock a few milliseconds after t_run
    stamps = [t_run + r["elapsed_s"] for r in log]
    t0, ends = stamps[1], stamps[2:]
    losses = [r["loss"] for r in log]
    record.update(
        window_t0=t0, step_ends=ends, window_losses=losses,
        input_stall_s=(stall1 - stall0) * steps / (steps + 2),
        compiles=compile_log, device=inproc.device_info())
    with open(os.path.join(args.out, "worker_record.json"), "w",
              encoding="utf-8") as f:
        json.dump(record, f)
    print("BENCH_RECORD " + os.path.join(args.out, "worker_record.json"),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
