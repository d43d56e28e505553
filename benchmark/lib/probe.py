"""Fixed probe directions for comparing two gradients that live in
different processes without moving either: K sign patterns per leaf, made
from the element's index by an integer hash (no random numbers to draw, so
a pass costs one read of the leaf). `<g, r_j>` is about |g| in size, and
`<g - g', r_j>` about |g - g'|: the gap between two gradients' projections
is linear in their difference, where the gap between their norms is
quadratic and hardly sees rounding noise. Used by the train worker (on the
program's gradient) and by the reference alike; benchmark code both times.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

K = 16


def _signs(n: int, j: int):
    i = jnp.arange(n, dtype=jnp.uint32)
    h = i * jnp.uint32(2654435761) + jnp.uint32((j * 40503 + 977) % 2 ** 32)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0x5BD1E995)
    h = h ^ (h >> 15)
    return 1.0 - 2.0 * ((h >> 7) & 1).astype(jnp.float32)


@jax.jit
def projections(x):
    """The K projections of one leaf onto its probe directions, float32."""
    flat = x.reshape(-1).astype(jnp.float32)
    return jnp.stack([jnp.sum(flat * _signs(flat.shape[0], j))
                      for j in range(K)])


def projection_gap(prog: dict, ref: dict, ref_norms: dict) -> tuple:
    """Worst leaf's root-mean-square difference between the program's and
    the reference's projections, against the reference's gradient norm of
    that leaf or of the median leaf, whichever is larger."""
    import statistics
    med = statistics.median(ref_norms.values())
    worst, leaf = -1.0, ""
    for name, r in ref.items():
        p = prog[name]
        rms = (sum((a - b) ** 2 for a, b in zip(p, r)) / len(r)) ** 0.5
        gap = rms / max(ref_norms[name], med, 1e-30)
        if gap > worst:
            worst, leaf = gap, name
    return worst, leaf
