"""The comparison that decides `correct`, run in a process of its own once
the job has ended and released the chip (the reference needs the chip, and
`memory_peak_bytes` has to stay the program's).

  check.py train   --config C --traffic T --seed N --record worker_record.json
  check.py serve   --config C --seed N --sample sample.json [--control int8]
        the served tokens against the float32 reference's logits, and the
        replica's device bytes against the weights and K/V cache in the
        types the configuration states (a cache kept in int8 moves no
        logit by more than bfloat16 rounding does; its bytes show it).
        With --control the reference in int8 stands in the program's
        place: at each position of the same prompts and tokens, the token
        it puts first is read against the float32 reference
  check.py control-train --config C --traffic T --seeds a,b,c
        the training control: the reference in float8 put in the program's
        place, compared with the float32 reference (no window needed)

Every number compared is printed beside its limit on a `compare` line; the
last line is `CHECK <json>` with the verdict. Limits live in the
configuration file (`limits`), PERF.md gives the readings they were set
from. The reference and the byte counts are those of the configuration's
family (benchmark/families/<family>/reference.py and counts.py).
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import statistics
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(HERE))


def _setup_jax(rehearse: bool):
    import jax
    d = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    platform = jax.devices()[0].platform
    if platform != "tpu" and not rehearse:
        print(f"check: the reference needs the chip, found {platform}",
              file=sys.stderr)
        sys.exit(2)
    if platform != "cpu":
        if not d:
            d = os.path.join(ROOT, ".jax_cache")
            os.makedirs(d, exist_ok=True)
            jax.config.update("jax_compilation_cache_dir", d)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return jax


class Compare:
    def __init__(self):
        self.rows = []

    def add(self, *args, **kw) -> None:
        from lib import stats
        self.rows.append(stats.compared(*args, **kw))

    def finish(self, **extra) -> int:
        verdict = all(r["ok"] for r in self.rows) and bool(self.rows)
        print("CHECK " + json.dumps({"correct": verdict, "compared":
                                     self.rows, **extra}), flush=True)
        return 0


def norm_gap(prog: dict, ref: dict) -> tuple:
    """Worst leaf's |program norm - reference norm|, against the reference
    norm of that leaf or of the median leaf, whichever is larger (some
    gradients are all but zero)."""
    med = statistics.median(ref.values())
    worst, leaf = -1.0, ""
    for name, r in ref.items():
        gap = abs(prog[name] - r) / max(r, med, 1e-30)
        if gap > worst:
            worst, leaf = gap, name
    return worst, leaf


def _load(args) -> tuple:
    """(the configuration, its family's reference, its family's counts)."""
    from lib import spec
    with open(args.config, encoding="utf-8") as f:
        cfg = json.load(f)
    family = spec.load_family(args.config, cfg)
    return cfg, family.reference, family.counts


def _batches(traffic_mod, mix, vocab, seed, n):
    return [b["tokens"] for b in itertools.islice(
        traffic_mod.train_batches(mix, vocab, seed), n)]


def compare_training(cmp: Compare, prog: dict, ref: dict, limits: dict):
    from lib import probe
    losses, gnorm, dnorm = ref["losses"], ref["grad_norms"], ref["delta_norms"]
    p_losses = prog["first_losses"]
    gap = max(abs(a - b) for a, b in zip(p_losses, losses))
    cmp.add("loss_gap", gap, limits["loss_gap"],
            f"steps 1..{len(losses)}: program {p_losses} reference {losses}")
    g, leaf = norm_gap(prog["grad_norms"], gnorm)
    cmp.add("grad_norm_gap", g, limits["grad_norm_gap"],
            f"first gradient, worst leaf {leaf}")
    g, leaf = probe.projection_gap(prog["grad_proj"], ref["grad_proj"], gnorm)
    cmp.add("grad_proj_gap", g, limits["grad_proj_gap"],
            f"first gradient on {probe.K} fixed directions, worst leaf "
            f"{leaf}")
    g, leaf = norm_gap(prog["delta_norms"], dnorm)
    cmp.add("delta_norm_gap", g, limits["delta_norm_gap"],
            f"parameters' change after {len(losses)} steps, worst leaf "
            f"{leaf}")


def cmd_train(args) -> int:
    _setup_jax(args.rehearse)
    from lib import traffic
    cfg, reference, _ = _load(args)
    mix = json.load(open(args.traffic, encoding="utf-8"))
    prog = json.load(open(args.record, encoding="utf-8"))
    t = time.monotonic()
    n = len(prog["first_losses"])
    batches = _batches(traffic, mix, cfg["vocab_size"], args.seed, n)
    ref = reference.follow_training(cfg, batches, args.seed)
    cmp = Compare()
    compare_training(cmp, prog, ref, cfg["limits"])
    if prog.get("window_losses"):
        change = prog["window_losses"][-1] - prog["first_losses"][0]
        cmp.add("loss_change", change, cfg["limits"]["loss_change"],
                f"last loss of the window {prog['window_losses'][-1]} "
                f"less the first step's")
    return cmp.finish(check_s=time.monotonic() - t)


def cmd_control_train(args) -> int:
    """The reference in float8 in the program's place, on each seed."""
    _setup_jax(args.rehearse)
    from lib import traffic
    cfg, reference, _ = _load(args)
    mix = json.load(open(args.traffic, encoding="utf-8"))
    n = int(mix["check_steps"])
    verdicts = []
    for seed in [int(s) for s in args.seeds.split(",")]:
        batches = _batches(traffic, mix, cfg["vocab_size"], seed, n)
        ref = reference.follow_training(cfg, batches, seed)
        low = reference.follow_training(cfg, batches, seed, args.precision)
        prog = {"first_losses": low["losses"], "grad_norms":
                low["grad_norms"], "grad_proj": low["grad_proj"],
                "delta_norms": low["delta_norms"]}
        cmp = Compare()
        print(f"control seed {seed} precision {args.precision}")
        compare_training(cmp, prog, ref, cfg["limits"])
        verdicts.append(all(r["ok"] for r in cmp.rows))
    print("CONTROL " + json.dumps({"correct": verdicts}), flush=True)
    return 0


def cmd_serve(args) -> int:
    jax = _setup_jax(args.rehearse)
    import jax.numpy as jnp
    cfg, reference, counts = _load(args)
    sample = json.load(open(args.sample, encoding="utf-8"))
    t = time.monotonic()
    params = reference.init_on_device(cfg, args.seed)
    weights = sum(x.nbytes for x in jax.tree.leaves(params))
    widest, total = 0.0, 0
    for req in sample["requests"]:
        prompt, served = req["prompt"], req["tokens"]
        logits = reference.served_logits(
            params, prompt + served, len(prompt), cfg,
            pad_to=int(sample.get("pad_to", 256)))
        best = jnp.max(logits, axis=-1)
        if args.control == "int8":
            # the control: the reference in int8 in the program's place;
            # at each position, the token that it puts first
            low = reference.served_logits(
                params, prompt + served, len(prompt), cfg,
                pad_to=int(sample.get("pad_to", 256)), precision="int8")
            served = [int(t) for t in jnp.argmax(low, axis=-1)]
        got = jnp.take_along_axis(
            logits, jnp.asarray(served, jnp.int32)[:, None], axis=-1)[:, 0]
        gaps = best - got
        gap = float(jnp.max(gaps))
        off = int(jnp.sum(gaps > 0))
        print(f"  checked request {req['index']}: prompt {len(prompt)} + "
              f"{len(served)} served tokens, widest gap {gap:.5f}, {off} "
              f"tokens not the reference's first", flush=True)
        widest = max(widest, gap)
        total += len(served)
    cmp = Compare()
    cmp.add("served_logit_gap", widest, cfg["limits"]["served_logit_gap"],
            f"widest gap by which a served token's logit lies below the "
            f"reference's best: {total} served tokens of "
            f"{len(sample['requests'])} requests")
    cmp.add("checked_tokens", total, cfg["limits"]["checked_tokens"],
            "served tokens compared", at_most=False)
    run = cfg["run"]
    cache = counts.cache_bytes(cfg, run["slots"], run["token_budget"],
                               jnp.dtype(cfg["torch_dtype"]).itemsize)
    resident = int(sample["resident_bytes"])
    cmp.add("resident_bytes_gap", abs(resident - weights - cache)
            / (weights + cache), cfg["limits"]["resident_bytes_gap"],
            f"the replica's peak device bytes {resident} against the "
            f"reference's weights {weights} + a K/V cache of {cache} in "
            f"{cfg['torch_dtype']}, the type the configuration states")
    return cmp.finish(check_s=time.monotonic() - t)


def main() -> int:
    p = argparse.ArgumentParser()
    sub = p.add_subparsers(dest="cmd", required=True)
    for name in ("train", "serve", "control-train"):
        s = sub.add_parser(name)
        s.add_argument("--config", required=True)
        s.add_argument("--rehearse", action="store_true")
        if name != "serve":
            s.add_argument("--traffic", required=True)
        if name == "control-train":
            s.add_argument("--seeds", required=True)
            s.add_argument("--precision", default="fp8")
        else:
            s.add_argument("--seed", type=int, required=True)
        if name == "train":
            s.add_argument("--record", required=True)
        if name == "serve":
            s.add_argument("--sample", required=True)
            s.add_argument("--control", default="none",
                           choices=("none", "int8"))
    args = p.parse_args()
    return {"train": cmd_train, "serve": cmd_serve,
            "control-train": cmd_control_train}[args.cmd](args)


if __name__ == "__main__":
    sys.exit(main())
