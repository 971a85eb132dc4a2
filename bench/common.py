"""What every path shares: logging, host spans, the measured window, the
device's memory peak and the numbers that decide ``correct``."""

from __future__ import annotations

import collections
import sys
import time

import numpy as np


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def span(name: str):
    """A host span in the profiler's trace (free when nothing traces)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


def run_window(issue, seconds: float, *, in_flight: int = 1,
               min_steps: int = 1, max_steps: int | None = None) -> dict:
    """Issue whole steps until ``seconds`` have passed, then issue nothing
    more, wait for every step issued, and read the clock after that wait.

    ``issue()`` enqueues one step and returns something to block on. The
    host keeps ``in_flight`` steps queued ahead of the one it waits for,
    so that the chip runs on while the host stands still. Returns the
    steps issued and the window's seconds: all of that work over all of
    that time."""
    import jax
    queue: collections.deque = collections.deque()
    steps = 0
    t0 = time.perf_counter()
    while True:
        with span("bench.issue"):
            queue.append(issue())
        steps += 1
        if len(queue) > in_flight:
            with span("bench.block"):
                jax.block_until_ready(queue.popleft())
        done = time.perf_counter() - t0 >= seconds and steps >= min_steps
        if done or (max_steps is not None and steps >= max_steps):
            break
    with span("bench.block"):
        jax.block_until_ready(list(queue))
    return {"steps": steps, "seconds": time.perf_counter() - t0}


def memory_peak_bytes() -> int:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks))


def worst_rel(out, ref) -> float:
    """The widest elementwise gap between ``out`` and ``ref``, over the
    root mean square of ``ref``."""
    out = np.asarray(out, np.float64)
    ref = np.asarray(ref, np.float64)
    rms = max(float(np.sqrt(np.mean(ref * ref))), 1e-30)
    return float(np.max(np.abs(out - ref)) / rms)


def token_gaps(ref_logits, served) -> np.ndarray:
    """For each position, how far the served token's reference logit lies
    below the reference's best, in units of that row's standard
    deviation. ``ref_logits`` (..., V), ``served`` (...) token ids."""
    ref = np.asarray(ref_logits, np.float64)
    got = np.take_along_axis(ref, np.asarray(served)[..., None], -1)[..., 0]
    return (ref.max(-1) - got) / np.maximum(ref.std(-1), 1e-30)
