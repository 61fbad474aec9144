"""Host spans and per-solve counters, on the profiler's own clock.

A span is a ``jax.profiler.TraceAnnotation``.  While no profiler runs it
costs next to nothing; under ``jax.profiler.trace`` it lands on the host
line of the trace beside the device's ops, so that device idle time can be
laid against what the host was doing.  Device ops are named with
``jax.named_scope`` where they are traced (``cpapr.pi``, ``cpapr.layout``,
``cpapr.phi``, ``cpapr.epilogue``), which reaches each op's ``tf_op``.

Counters are plain Python integers.  Their totals go onto the span that
bounds them when it ends, as the span's attributes.  Nothing here reads a
device value, waits on the device or compiles a program.
"""
from __future__ import annotations

import contextlib

import jax

__all__ = ["Counters", "counted", "span"]


def span(name: str, **attrs):
    """A host span ``name`` with ``attrs``; use it as a context manager."""
    return jax.profiler.TraceAnnotation(name, **attrs)


class Counters:
    """Counts of one solve: ``add`` a count, or ``read`` a device value on
    the host, which counts as one host sync."""

    def __init__(self):
        self.totals: dict = {}

    def add(self, name: str, n: int = 1) -> None:
        self.totals[name] = self.totals.get(name, 0) + int(n)

    def read(self, cast, value):
        """``cast(value)`` (``float``, ``int`` or ``bool`` of a device
        value), counted under ``host_syncs``."""
        self.add("host_syncs")
        return cast(value)


@contextlib.contextmanager
def counted(name: str, counters: Counters, **attrs):
    """A span whose counters' totals become its attributes at its end."""
    with span(name, **attrs) as s:
        try:
            yield counters
        finally:
            s.set_metadata(**counters.totals)
