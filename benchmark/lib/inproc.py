"""Helpers for the processes the benchmark launches (the train worker and
the serving replica). They run beside the program, in the process that
holds the chip, so unlike the harness they may import jax. They record
from outside the program: compile events, the device, a profiler trace.
"""

from __future__ import annotations

import json
import os
import threading
import time

COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")


def jax_seed(seed: int) -> int:
    """--seed may exceed 32 signed bits; jax.random.PRNGKey may not."""
    return int(seed) % 2147483647


def install_compile_log() -> list:
    """Every XLA compile, and every load from the persistent cache, from
    now on: [monotonic seconds, event, duration] appended as they happen.
    `compiles_in_window` counts those stamped inside the window."""
    import jax.monitoring
    log: list = []

    def on_duration(event: str, secs: float, **_kw) -> None:
        if event in COMPILE_EVENTS:
            log.append([time.monotonic(), event.rsplit("/", 1)[-1], secs])

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    return log


def device_info() -> dict:
    import jax
    devs = jax.local_devices()
    peak = 0
    for d in devs:
        try:
            stats = d.memory_stats() or {}
        except Exception:  # noqa: BLE001 — a backend without the call
            stats = {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    if not peak:
        # a backend without memory statistics (the CPU rehearsal): what
        # is resident now
        peak = sum(x.nbytes for x in jax.live_arrays())
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def seeded_init(init, config, seed: int):
    """The program's own initialiser `init(config, key)`, on the device
    from the seed, in the dtype the weights are served or trained in: one
    fused jitted call a leaf (the other leaves are dead code in it). The
    whole tree in one call needs 30 GiB of temporaries for 16 layers on a
    v5e by the compiler's memory_analysis(); a leaf at a time needs none."""
    import jax
    treedef = jax.tree.structure(jax.eval_shape(
        lambda: init(config, jax.random.PRNGKey(0))))
    return jax.tree.unflatten(
        treedef, [leaf for _, leaf in seeded_leaves(init, config, seed)])


def seeded_leaves(init, config, seed: int):
    """(path, leaf) of `init(config, key)` for the seed's key, each leaf made
    by its own jitted program as it is asked for. The key is an argument,
    never a constant the compiler could fold."""
    import jax
    key = jax.random.PRNGKey(jax_seed(seed))
    flat, _ = jax.tree_util.tree_flatten_with_path(
        jax.eval_shape(lambda k: init(config, k), key))

    def pick(tree, path):
        for k in path:
            tree = tree[k.key]
        return tree

    for path, _ in flat:
        yield path, jax.jit(
            lambda k, path=path: pick(init(config, k), path))(key)


def watch_requests(ctl_dir: str, compile_log: list) -> threading.Thread:
    """Serve what the harness drops into `ctl_dir` while the program runs:
    `trace_request.json` {"seconds": s} -> profile this process for s
    seconds into `ctl_dir/trace`, then write `trace_done` (only the
    process that holds the chip can trace it); `report_request` -> write
    `report.json` with the device, its peak memory and the compile log."""
    def loop() -> None:
        import jax
        trace_req = os.path.join(ctl_dir, "trace_request.json")
        report_req = os.path.join(ctl_dir, "report_request")
        while True:
            if os.path.exists(trace_req):
                with open(trace_req, encoding="utf-8") as f:
                    seconds = float(json.load(f)["seconds"])
                os.remove(trace_req)
                jax.profiler.start_trace(os.path.join(ctl_dir, "trace"))
                time.sleep(seconds)
                jax.profiler.stop_trace()
                with open(os.path.join(ctl_dir, "trace_done"), "w",
                          encoding="utf-8") as f:
                    f.write("done\n")
            if os.path.exists(report_req):
                os.remove(report_req)
                tmp = os.path.join(ctl_dir, "report.json.tmp")
                with open(tmp, "w", encoding="utf-8") as f:
                    json.dump({"device": device_info(),
                               "compiles": list(compile_log)}, f)
                os.replace(tmp, os.path.join(ctl_dir, "report.json"))
            time.sleep(0.05)

    t = threading.Thread(target=loop, name="bench-requests", daemon=True)
    t.start()
    return t
