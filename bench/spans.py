"""Read the program's spans and named scopes from one profiler trace.

The program marks its layer boundaries with ``repro.tracing.region``: a
host span in the profiler's trace, on the device planes' clock, and a
named scope in the ``op_name`` of every op compiled inside it. ``read``
takes a ``jax.profiler.ProfileData`` and returns, inside the
``bench.window`` span (found, like the device planes and their op lines,
as ``bench/trace.py`` finds them):

- ``spans``: for each span in ``SPANS``, how many began in the window and
  their host self time up to the window's end: the time not covered by a
  listed span nested in them on the same thread;
- ``idle_by_span``: chip 0's idle time, every gap of it, split by the
  innermost listed span under way (``host:other`` where none is);
- ``idle_gaps``: chip 0's ten longest gaps, each named by the innermost
  listed span under way at its midpoint;
- ``scopes``: the device seconds of the op events under each scope in
  ``REGIONS``, keyed by the innermost listed scope in the op's ``op_name``
  (``none`` for an op under no listed scope), averaged over the chips
  that ran anything;
- ``window_s``, ``busy_s`` (as ``trace.reduce_profile`` counts them) and
  ``steps``, the ``bench.issue`` spans that began in the window;
- ``kernel_lead_s``: how far the k-th ``matmul_int8`` op on chip 0 starts
  before the k-th ``matmul_int8.kernel`` span that dispatched it, at most
  (None where the window holds neither): above 0, the device's clock in
  the trace runs ahead of the host's, and idle time is placed to that
  accuracy.

A TPU trace keeps an op's ``op_name`` in the ``tf_op`` stat of the op
event's metadata, which ``ProfileData`` does not expose: ``op_names``
reads it from the serialized trace itself (``XSpace`` in
``tsl/profiler/protobuf/xplane.proto``), keyed by the event's name, the
HLO instruction's text. ``readings`` turns a ``read`` into the layer
numbers the spans are for. ``python -m bench.spans <file.xplane.pb>``,
from the repo's root, prints both for a trace on disk.
"""

from __future__ import annotations

import collections
import json
import sys

from bench import trace

#: The program's regions (``repro.tracing.region``).
REGIONS = ("matmul_int8.quantize", "matmul_int8.pad", "matmul_int8.kernel",
           "flash_attention.layout", "flash_attention.kernel",
           "embed", "attn", "kv_update", "mlp", "lm_head")
#: Host spans whose self time is read and that name idle time: the
#: harness's own and the program's.
SPANS = trace.HOST_SPANS + REGIONS
OTHER = "host:other"
NONE = "none"
#: The stat of an op event's metadata that holds its ``op_name``.
OP_NAME_STAT = "tf_op"


def _window(pd) -> tuple[int, int]:
    win = [e for p in pd.planes if p.name.startswith("/host:")
           for line in p.lines for e in line.events
           if e.name == trace.WINDOW]
    if not win:
        raise RuntimeError(f"no {trace.WINDOW!r} span in the trace")
    return min(e.start_ns for e in win), max(e.end_ns for e in win)


def _host_spans(pd, w0: int, w1: int) -> list[tuple[int, int, str, str]]:
    """Listed spans that begin in [w0, w1), cut at w1:
    [(start_ns, end_ns, name, line)]."""
    return [(e.start_ns, min(e.end_ns, w1), e.name, line.name)
            for p in pd.planes if p.name.startswith("/host:")
            for line in p.lines for e in line.events
            if e.name in SPANS and w0 <= e.start_ns < w1]


def _device_ops(pd, w0: int, w1: int):
    """For each TPU plane that ran anything in the window, in name order:
    its op events there, cut to the window, control-flow ops left out:
    [(start_ns, end_ns, event)]."""
    planes = sorted((p for p in pd.planes
                     if p.name.startswith(trace.DEVICE_PREFIX)),
                    key=lambda p: p.name)
    for plane in planes:
        lines = [ln for ln in plane.lines if ln.name == trace.OPS_LINE] or \
            [ln for ln in plane.lines if ln.name.endswith(" Ops")]
        ops = [(max(ev.start_ns, w0), min(ev.end_ns, w1), ev)
               for line in lines for ev in line.events
               if min(ev.end_ns, w1) > max(ev.start_ns, w0)
               and not trace._container(ev)]
        if ops:
            yield ops


def self_times(spans) -> dict:
    """``{name: {"count", "self_s"}}`` of ``spans`` [(start_ns, end_ns,
    name, line)]: each span's duration less the parts its listed children
    on the same line cover."""
    out = collections.defaultdict(lambda: {"count": 0, "self_s": 0.0})
    by_line = collections.defaultdict(list)
    for s, e, name, line in spans:
        by_line[line].append((s, -e, name))
    for evs in by_line.values():
        stack: list[list] = []            # [end_ns, name, self_ns]
        for s, neg_e, name in sorted(evs):
            e = -neg_e
            while stack and stack[-1][0] <= s:
                _close(out, stack.pop())
            if stack:
                stack[-1][2] -= min(e, stack[-1][0]) - s
            stack.append([e, name, e - s])
        while stack:
            _close(out, stack.pop())
    return dict(out)


def _close(out, frame) -> None:
    _, name, self_ns = frame
    out[name]["count"] += 1
    out[name]["self_s"] += self_ns * 1e-9


def innermost(spans, w0: int, w1: int) -> list[tuple[int, int, str]]:
    """[(start, end, label)] covering [w0, w1]: at each instant the span
    under way that began last, or ``host:other``."""
    bounds = sorted({w0, w1, *(t for s, e, _, _ in spans
                               for t in (s, e) if w0 < t < w1)})
    starts = sorted((s, e, name) for s, e, name, _ in spans)
    out: list[list] = []
    active: list[tuple] = []
    j = 0
    for a, b in zip(bounds, bounds[1:]):
        while j < len(starts) and starts[j][0] <= a:
            active.append(starts[j])
            j += 1
        active = [sp for sp in active if sp[1] > a]
        label = max(active, key=lambda sp: (sp[0], -sp[1]))[2] \
            if active else OTHER
        if out and out[-1][2] == label:
            out[-1][1] = b
        else:
            out.append([a, b, label])
    return [tuple(x) for x in out]


def split_idle(gaps, timeline) -> dict:
    """Seconds of ``gaps`` [(start, end)] under each label of
    ``timeline`` (``innermost``, sorted by start)."""
    out = collections.defaultdict(float)
    j = 0
    for g0, g1 in sorted(gaps):
        while j < len(timeline) and timeline[j][1] <= g0:
            j += 1
        k = j
        while k < len(timeline) and timeline[k][0] < g1:
            s, e, label = timeline[k]
            out[label] += (min(e, g1) - max(s, g0)) * 1e-9
            k += 1
    return dict(out)


def _label_at(timeline, t: float) -> str:
    return next((label for s, e, label in timeline if s <= t < e), OTHER)


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes, i: int, end: int):
    """(field number, value) of the protobuf message in buf[i:end]: an int
    for a varint, a (start, end) span for a length-delimited field."""
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wire in (1, 5):
            value, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"wire type {wire} in an xplane message")
        yield key >> 3, value


def _text(buf: bytes, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def op_names(data: bytes) -> dict[str, str]:
    """``{event name: op_name}`` of every op on a TPU plane of the
    serialized ``XSpace`` ``data``: XSpace.planes (1); XPlane.name (2),
    .event_metadata (4) and .stat_metadata (5), maps whose entries hold a
    key (1) and a value (2); XEventMetadata.name (2) and .stats (5);
    XStatMetadata.name (2); XStat.metadata_id (1) and .str_value (5)."""
    out = {}
    for num, plane in _fields(data, 0, len(data)):
        if num != 1:
            continue
        name, events, stat_names = "", [], {}
        for f, v in _fields(data, *plane):
            if f == 2:
                name = _text(data, v)
            elif f in (4, 5):
                entry = dict(_fields(data, *v))
                meta = dict(_fields(data, *entry[2])) if 2 in entry else {}
                if f == 5:
                    stat_names[entry.get(1)] = _text(data,
                                                     meta.get(2, (0, 0)))
                elif 2 in meta:
                    stats = [dict(_fields(data, *st))
                             for g, st in _fields(data, *entry[2]) if g == 5]
                    events.append((_text(data, meta[2]), stats))
        if not name.startswith(trace.DEVICE_PREFIX):
            continue
        for ev_name, stats in events:
            for st in stats:
                if stat_names.get(st.get(1)) == OP_NAME_STAT and 5 in st:
                    out[ev_name] = _text(data, st[5]).rstrip(":")
    return out


def scope_of(name: str) -> str:
    """The innermost listed scope of an ``op_name``
    (``jit(step)/while/body/attn/kv_update/mul`` -> ``kv_update``)."""
    for part in reversed(name.split("/")):
        if part in REGIONS:
            return part
    return NONE


def read(pd, names: dict[str, str]) -> dict:
    """What the module docstring lists, of a ``ProfileData``, with each
    op event's ``op_name`` looked up by its name in ``names``
    (``op_names``)."""
    w0, w1 = _window(pd)
    spans = _host_spans(pd, w0, w1)
    gaps, busy, kernels = None, [], []
    scopes = collections.defaultdict(float)
    for evs in _device_ops(pd, w0, w1):
        for s, e, ev in evs:
            scopes[scope_of(names.get(ev.name, ""))] += (e - s) * 1e-9
        merged = trace._union((s, e) for s, e, _ in evs)
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        if gaps is None:
            kernels = sorted(s for s, _, ev in evs
                             if trace._family(ev, ["matmul_int8"]))
            edges = [w0] + [x for iv in merged for x in iv] + [w1]
            gaps = [(edges[k], edges[k + 1])
                    for k in range(0, len(edges), 2)
                    if edges[k + 1] > edges[k]]
    if not busy:
        raise RuntimeError("no device op ran inside the window")
    timeline = innermost(spans, w0, w1)
    issued = sorted(s for s, _, name, _ in spans
                    if name == "matmul_int8.kernel")
    leads = [h - d for h, d in zip(issued, kernels)]
    longest = sorted(gaps, key=lambda g: g[1] - g[0], reverse=True)[:10]
    times = self_times(spans)
    return {"window_s": (w1 - w0) * 1e-9,
            "busy_s": sum(busy) / len(busy),
            "steps": times.get("bench.issue", {}).get("count", 0),
            "spans": times,
            "idle_by_span": split_idle(gaps, timeline),
            "idle_gaps": [[_label_at(timeline, (s + e) / 2), (e - s) * 1e-9]
                          for s, e in longest],
            "scopes": {k: v / len(busy) for k, v in scopes.items()},
            "kernel_lead_s": max(leads) * 1e-9 if leads else None}


def readings(r: dict) -> dict:
    """The layer numbers of a ``read``, each where the trace holds what it
    reads: ``plan_quantize_host_ms``, the quantize spans' self time a step;
    ``quantize_idle_share``, chip-0 idle under them over the window (%);
    ``kv_update_share`` and ``flash_layout_share``, the device time under
    ``kv_update`` and ``flash_attention.layout`` over busy time (%)."""
    out = {}
    quantize = r["spans"].get("matmul_int8.quantize")
    if quantize and r["steps"]:
        out["plan_quantize_host_ms"] = quantize["self_s"] * 1e3 / r["steps"]
    if "matmul_int8.quantize" in r["idle_by_span"]:
        out["quantize_idle_share"] = \
            100.0 * r["idle_by_span"]["matmul_int8.quantize"] / r["window_s"]
    for name, scope in (("kv_update_share", "kv_update"),
                        ("flash_layout_share", "flash_attention.layout")):
        if scope in r["scopes"]:
            out[name] = 100.0 * r["scopes"][scope] / r["busy_s"]
    return out


def check_calls(r: dict, counted: dict) -> None:
    """Raise unless the window holds a ``matmul_int8.quantize`` span for
    every eager call that ``repro.tracing`` counted in it: else the
    profiler dropped events, and what is read from spans is wrong."""
    calls = counted.get("matmul_int8.calls")
    seen = r["spans"].get("matmul_int8.quantize", {}).get("count", 0)
    if calls is not None and seen != calls:
        raise RuntimeError(f"the window counted {calls} matmul_int8 calls "
                           f"but the trace holds {seen} "
                           f"matmul_int8.quantize spans")


def reduce(path: str) -> dict:
    """``read`` of the trace file at ``path``."""
    import jax
    with open(path, "rb") as f:
        data = f.read()
    return read(jax.profiler.ProfileData.from_serialized_xspace(data),
                op_names(data))


if __name__ == "__main__":
    r = reduce(sys.argv[1])
    json.dump(dict(r, readings=readings(r)), sys.stdout, indent=1)
    print()
