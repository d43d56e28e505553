"""Persistent XLA compile cache: the one place that decides where it is.

A cold llama3_1b_proxy train-step compile is most of a container's
bring-up, and a chip call that starts with no compiled code pays it for
every program. Trainer, serving replica and bench children all call
`enable_compile_cache` right before their first jit, so the Nth
identical process loads what the first one compiled.

Where the directory comes from, in this order:

1. `$JAX_COMPILATION_CACHE_DIR` — jax reads it itself; when it is set
   the cache lives there and NO code of this repo sets another directory.
2. `$TONY_JAX_CACHE_DIR`, which the executor renders into the user env
   from `tony.executor.jax-cache-dir` when the job set that key
   (executor/runtimes.py).
3. `.jax_cache/` in the checkout, computed from this package's own
   location: never from the working directory (a container's is
   `<workdir>/<appId>/containers/<task>`, new on every submission) and
   never from a temp name, pid or time — the path is part of the cache
   key, so a directory that moves never hits.

2 and 3 apply to accelerator backends only. On the CPU backend every
cache HIT makes XLA log a multi-kilobyte "machine type used for XLA:CPU
compilation doesn't match" error (jaxlib 0.9.0 compares two spellings of
the same feature list), which buries a test worker's log; a CPU compile
of the programs here is seconds anyway. A user who sets
`$JAX_COMPILATION_CACHE_DIR` gets jax's own behaviour on any backend.
"""

from __future__ import annotations

import logging
import os

LOG = logging.getLogger(__name__)

JAX_ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache(jax=None) -> str | None:
    """Switch jax's persistent compilation cache on and return its
    directory; None on the CPU backend (module docstring) and when the
    directory cannot be made (a read-only checkout): the cache is an
    optimization, never a dependency. Initialises the backend to ask
    which it is, so call it where the device is about to be touched —
    after `jax.distributed.initialize` in a multi-process job."""
    from tony_tpu import constants as C

    if jax is None:
        import jax  # noqa: F811 — deferred: callers may be jax-free
    d = os.environ.get(JAX_ENV, "")
    if not d:
        if jax.default_backend() == "cpu":
            LOG.info("no persistent compile cache on the cpu backend")
            return None
        d = os.environ.get(C.JAX_CACHE_DIR, "") or CHECKOUT_CACHE_DIR
        try:
            os.makedirs(d, exist_ok=True)
        except OSError as e:
            LOG.warning("persistent compile cache unavailable at %s: %s",
                        d, e)
            return None
        jax.config.update("jax_compilation_cache_dir", d)
    # cache even fast compiles (a 1k-wide gang recompiling 0.6 s kernels
    # still pays them a thousand times) and any entry size
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    LOG.info("persistent XLA compile cache at %s", d)
    return d
