"""Spans on the profiler's clock: `jax.profiler.TraceAnnotation`, tiled.

No second tracing system. A span written here lands on the host plane of
the same `.xplane.pb` a `jax.profiler.start_trace` session writes the
device's events to, so one reader lays the two over each other (the
planes' clocks can still differ: on a v5e the device's read ~2 ms early,
which `benchmark/lib/hostspans.py` measures and takes out). With no
session open an annotation costs one flag check (~0.5 us with two
attributes). Imported only by processes that hold the chip already (the
serving engine and its front end): the control plane never imports jax.
"""

from __future__ import annotations

from jax.profiler import TraceAnnotation as span  # noqa: F401


class Phases:
    """One parent span tiled by consecutive leaf spans.

    `enter(name)` closes the open leaf and opens the next in one move, so
    no instant between two phases lies outside a leaf; the parent's own
    time is then only what runs before the first `enter` and after
    `leave()`. Attributes given here or by `set()` go on the parent and on
    every leaf opened afterwards (`set` also reaches the leaf that is
    open), so a step's spans share `step` and a request's `request_id`.
    What belongs to one leaf alone (a decode step's riders on its
    `decode.dispatch`) is given to `enter()`: it goes on that leaf and is
    kept nowhere.
    """

    def __init__(self, name: str, **attrs):
        self._attrs = attrs
        self._parent = span(name, **attrs)
        self._leaf = None

    def __enter__(self) -> "Phases":
        self._parent.__enter__()
        return self

    def __exit__(self, *exc) -> None:
        self.leave()
        self._parent.__exit__(*exc)

    def enter(self, name: str, **attrs) -> None:
        self.leave()
        self._leaf = span(name, **self._attrs, **attrs)
        self._leaf.__enter__()

    def leave(self) -> None:
        if self._leaf is not None:
            self._leaf.__exit__(None, None, None)
            self._leaf = None

    def set(self, **attrs) -> None:
        """Attributes known only once the span is open (a request's id
        after the dequeue, a step's admissions at its end)."""
        self._attrs = {**self._attrs, **attrs}
        self._parent.set_metadata(**attrs)
        if self._leaf is not None:
            self._leaf.set_metadata(**attrs)

    def note(self, **attrs) -> None:
        """Attributes for the parent alone."""
        self._parent.set_metadata(**attrs)
