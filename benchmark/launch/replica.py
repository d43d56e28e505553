"""The serving replica a serving cell launches: `python -m tony_tpu.serve`
(the program's own entry, `tony_tpu.serve.__main__.main`) with the cell's
configuration registered as a preset and its weights made on the device
from --seed, both by the `program.py` of the configuration's family
(benchmark/families/). Everything else — engine, front end, registration
with the AM, shutdown — is the program's.

  --control int8-cache   the lower-precision control: the program's own
                         int8 K/V cache (`--quant-cache`). It moves the
                         served logits less than bfloat16 rounding does,
                         so the check tells it by the replica's device
                         bytes, and `correct` comes out false
  --sabotage flip        (tests only) alters every token where it is
                         sampled
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

print("BENCH_START " + json.dumps({"t": time.monotonic()}), flush=True)

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))                    # benchmark/
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))   # checkout


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--config", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--ctl-dir", required=True)
    p.add_argument("--control", default="none",
                   choices=("none", "int8-cache"))
    p.add_argument("--sabotage", default="none", choices=("none", "flip"))
    args = p.parse_args()
    with open(args.config, encoding="utf-8") as f:
        cfg = json.load(f)

    from lib import inproc, spec
    compile_log = inproc.install_compile_log()
    from tony_tpu.serve import __main__ as serve_main
    family = spec.load_family(args.config, cfg)
    preset = family.program.serving(cfg, args.seed)
    if args.sabotage == "flip":
        from tony_tpu.serve import engine
        sample, vocab = engine._sample, cfg["vocab_size"]
        engine._sample = lambda *a, **kw: (sample(*a, **kw) + 1) % vocab
    inproc.watch_requests(args.ctl_dir, compile_log)
    run = cfg["run"]
    argv = ["--config", preset, "--slots", str(run["slots"]),
            "--token-budget", str(run["token_budget"]),
            "--queue-depth", str(run["queue_depth"])]
    if args.control == "int8-cache":
        argv += ["--quant-cache"]
    return serve_main.main(argv)


if __name__ == "__main__":
    sys.exit(main())
