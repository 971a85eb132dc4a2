"""Shares that the per-layer readers report, from a traced run's record.

``rec`` holds the traced window (``window_s``, ``busy_s``, ``steps``),
the kernel families' device time (``families``: events and seconds), the
path's work (``work``: the whole step's, and each kernel call's of one
step) and the chip's peaks. Every share is in percent; none is clipped.
"""

from __future__ import annotations

from bench.peaks import rate
from bench.work import least_s


def idle_share(rec: dict) -> float:
    return 100.0 * (1.0 - rec["busy_s"] / rec["window_s"])


def step_mfu(rec: dict) -> float | None:
    """The whole step's operations over the window, as a share of the
    peak of the step's precision."""
    step = rec["work"].get("step")
    if step is None:
        return None
    return 100.0 * step["ops"] * rec["steps"] / (
        rec["window_s"] * rate(rec["peaks"], step["precision"]))


def step_roofline(rec: dict) -> float | None:
    """The step's least time (operations or bytes, whichever bounds it)
    over the time a step took."""
    step = rec["work"].get("step")
    if step is None:
        return None
    return 100.0 * least_s(step, rec["peaks"]) * rec["steps"] / \
        rec["window_s"]


def plan_mfu(rec: dict) -> float | None:
    """Each kernel call's operations at the peak of its own precision,
    summed over the step, as a share of the time a step took."""
    kernels = rec["work"].get("kernels") or {}
    if not kernels:
        return None
    busy = sum(w["ops"] / rate(rec["peaks"], w["precision"])
               for calls in kernels.values() for w in calls)
    return 100.0 * busy * rec["steps"] / rec["window_s"]


def kernel_roofline(rec: dict, family: str) -> float | None:
    """The family's least time over the summed device time of its events.
    None where the step makes no call of it; an error where it makes
    calls and the trace shows no event."""
    calls = (rec["work"].get("kernels") or {}).get(family)
    if not calls:
        return None
    seen = rec["families"].get(family) or {}
    if not seen.get("events"):
        raise RuntimeError(f"the step called {family} {len(calls)} times "
                           f"but the trace has no event of it")
    least = sum(least_s(w, rec["peaks"]) for w in calls) * rec["steps"]
    return 100.0 * least / seen["seconds"]
