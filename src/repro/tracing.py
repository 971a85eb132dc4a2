"""The program's own spans, named scopes and counters.

``region(name)`` marks one layer boundary twice over: as a host span in
the profiler's trace (``jax.profiler.TraceAnnotation``), on the same clock
as the device's planes, and as a named scope (``jax.named_scope``), which
puts ``name`` into the ``op_name`` metadata of every op traced inside it.
Called eagerly, the span times the host's work; under ``jit`` the scope
names the compiled ops, and the span only times the trace. With no
profiler running a region costs a few microseconds.

``count(name)`` adds to a plain in-memory counter and ``counters()``
returns them all. A count made inside traced code runs once per trace,
not once per call, so the program counts eagerly only. ``jax.compiles``
counts the executables JAX builds or loads from its persistent cache
(the backend-compile event of ``jax.monitoring``), so that a caller can
see a compile inside a window it times.

Nothing is exported or written: the profiler keeps the spans and writes
them out when its trace stops (``jax.profiler.trace``).
"""

from __future__ import annotations

import collections
import contextlib

import jax

#: The ``jax.monitoring`` event timed around every backend compile (or
#: persistent-cache load) of an executable.
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_counts: collections.Counter = collections.Counter()


@contextlib.contextmanager
def region(name: str):
    """A host span and a named scope called ``name``."""
    with jax.profiler.TraceAnnotation(name), jax.named_scope(name):
        yield


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to the counter ``name``; call it eagerly only."""
    _counts[name] += n


def counters() -> dict[str, int]:
    """A copy of every counter."""
    return dict(_counts)


def _on_duration(event: str, duration_secs: float, **kwargs) -> None:
    if event == COMPILE_EVENT:
        _counts["jax.compiles"] += 1


jax.monitoring.register_event_duration_secs_listener(_on_duration)
