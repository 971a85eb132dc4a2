"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer readers
need.

The window is the host span ``bench.window`` on the ``/host:CPU`` plane.
Device time is the ``XLA Ops`` line of every ``/device:TPU:<n>`` plane
(or, where a runtime names it otherwise, its lines that end in " Ops"):
busy is the union of its events' intervals inside the window, averaged
over the chips that ran anything. A kernel family's events are those
named for the jitted function that wraps its ``pallas_call``
(``FAMILIES``): the compiled custom call takes that function's name
(``%matmul_int8.1 = ...``), whether the kernel is called alone or
inside a model's step. Control-flow ops (a ``while`` over the layers)
span the ops of their bodies and are left out of busy time and of the
top ops, which are counted by instruction name. Idle gaps, the
stretches of the window in which chip 0 ran nothing, are named by the
host span under way at their midpoint (``bench.issue``, ``bench.block``,
``bench.restore``).
"""

from __future__ import annotations

import collections

#: How each kernel family's events are recognised: the jitted functions
#: in ``kernels/*/kernel.py`` that call ``pallas_call``.
FAMILIES = {"matmul_int8": "matmul_int8",
            "flash_attention": "flash_attention_bh"}
WINDOW = "bench.window"
HOST_SPANS = ("bench.issue", "bench.block", "bench.restore")
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"


def _union(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _instruction(ev) -> str:
    """The HLO instruction's name: a TPU trace names each op event by the
    instruction's text (``%fusion.96 = bf16[...] fusion(...), ...``)."""
    return ev.name.split(" = ", 1)[0].lstrip("%")


def _container(ev) -> bool:
    """A control-flow op (``while``, ``conditional``, ``call``), whose
    event spans the events of the ops in its body."""
    return any(k in ev.name for k in (" while(", " conditional(", " call("))


def _family(ev, families) -> str | None:
    """The family whose wrapper names the instruction (``<fn>`` or
    ``<fn>.<n>``)."""
    base = _instruction(ev).split(".")[0]
    for fam in families:
        if base == FAMILIES.get(fam, fam):
            return fam
    return None


def reduce(path: str, families=()) -> dict:
    """``reduce_profile`` of the trace file at ``path``."""
    import jax
    return reduce_profile(jax.profiler.ProfileData.from_file(path), families)


def reduce_profile(pd, families=()) -> dict:
    """Window, busy time, kernel families, top device ops and the longest
    idle gaps of a ``jax.profiler.ProfileData``."""
    planes = list(pd.planes)
    host = [e for p in planes if p.name.startswith("/host:")
            for line in p.lines for e in line.events
            if e.name == WINDOW or e.name in HOST_SPANS]
    win = [e for e in host if e.name == WINDOW]
    if not win:
        raise RuntimeError(f"no {WINDOW!r} span in the trace")
    w0 = min(e.start_ns for e in win)
    w1 = max(e.end_ns for e in win)
    spans = [(e.start_ns, e.end_ns, e.name) for e in host
             if e.name in HOST_SPANS]
    devices = sorted((p for p in planes if p.name.startswith(DEVICE_PREFIX)),
                     key=lambda p: p.name)
    busy, fam = [], collections.defaultdict(lambda: [0, 0.0])
    ops = collections.defaultdict(float)
    gaps = []
    for i, plane in enumerate(devices):
        lines = [ln for ln in plane.lines if ln.name == OPS_LINE] or \
            [ln for ln in plane.lines if ln.name.endswith(" Ops")]
        ivs = []
        for line in lines:
            for ev in line.events:
                s, e = max(ev.start_ns, w0), min(ev.end_ns, w1)
                if e <= s or _container(ev):
                    continue
                ivs.append((s, e))
                ops[_instruction(ev)] += (e - s) * 1e-9
                f = _family(ev, families)
                if f is not None:
                    fam[f][0] += 1
                    fam[f][1] += (e - s) * 1e-9
        if not ivs:
            continue
        merged = _union(ivs)
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        if i == 0 or not gaps:
            edges = [w0] + [x for iv in merged for x in iv] + [w1]
            gaps = [(edges[k], edges[k + 1])
                    for k in range(0, len(edges), 2)
                    if edges[k + 1] > edges[k]]
    if not busy:
        found = {p.name: [ln.name for ln in p.lines] for p in planes}
        raise RuntimeError(f"no device op ran inside the window; planes "
                           f"and lines: {found}")

    def host_at(t: float) -> str:
        under = [(s, name) for s, e, name in spans if s <= t < e]
        return max(under)[1] if under else "host:other"

    gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": sum(busy) / len(busy),
        "chips": len(busy),
        "families": {f: {"events": n, "seconds": s}
                     for f, (n, s) in sorted(fam.items())},
        "device_ops": [[k, v] for k, v in
                       sorted(ops.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": [[host_at((s + e) / 2), (e - s) * 1e-9]
                      for s, e in gaps[:10]],
    }
