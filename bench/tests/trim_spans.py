"""Trim a trace recorded by ``bench/run.py --trace 1 --keep-trace DIR`` (or
``bench/run_spans.py``) into a small fixture that ``bench/spans.py`` and
the reducer can read.

    python bench/tests/trim_spans.py <xplane.pb> <out.xplane.pb> \\
        [max_ops] [--all-stats]

Keeps, of the ``/host:CPU`` plane, the ``bench.window`` span and every
span ``bench/spans.py`` lists (the harness's ``bench.*`` and the
program's regions); of every TPU plane the ``XLA Ops`` line: its first
``max_ops`` events inside the window (0 keeps all; the window is then
cut to end with the last of them) with their stats. Of each op's
metadata it keeps what the readers read: the HLO instruction's name,
the op's kind where it is a control-flow op, the ``op_name`` where the
HLO text holds it, and the stats that hold an ``op_name`` (``tf_op``);
``--all-stats`` keeps every stat and the whole name. Needs TensorFlow's
xplane protobuf, which the tests themselves do not.
"""

from __future__ import annotations

import re
import sys

from bench import spans
from bench.tests.trim_trace import short

OP_NAME_STATS = ("tf_op",)


def _name(name: str, whole: bool) -> str:
    if whole:
        return name
    m = re.search(r'op_name="[^"]*"', name)
    return short(name) + (f", metadata={{{m.group(0)}}}" if m else "")


def _copy_stats(src, dst, plane, new_plane, keep) -> None:
    for st in src.stats:
        sm = plane.stat_metadata[st.metadata_id]
        if keep is not None and sm.name not in keep:
            continue
        dst.stats.add().CopyFrom(st)
        new_plane.stat_metadata[st.metadata_id].CopyFrom(sm)
        if st.HasField("ref_value"):
            ref = plane.stat_metadata[st.ref_value]
            new_plane.stat_metadata[st.ref_value].CopyFrom(ref)


def trim(src: str, dst: str, max_ops: int = 3000,
         all_stats: bool = False) -> None:
    from tensorflow.tsl.profiler.protobuf import xplane_pb2
    keep = None if all_stats else OP_NAME_STATS
    space = xplane_pb2.XSpace()
    with open(src, "rb") as f:
        space.ParseFromString(f.read())
    host = next(p for p in space.planes if p.name == "/host:CPU")
    names = set(spans.SPANS) | {"bench.window"}
    kept = [(line, e) for line in host.lines for e in line.events
            if host.event_metadata[e.metadata_id].name in names]
    win = next((ln, e) for ln, e in kept
               if host.event_metadata[e.metadata_id].name == "bench.window")
    w0 = win[0].timestamp_ns * 1000 + win[1].offset_ps
    w1 = cut = w0 + win[1].duration_ps
    out = xplane_pb2.XSpace()
    for plane in space.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        new = out.planes.add(id=plane.id, name=plane.name)
        used = set()
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            nl = new.lines.add(id=line.id, name=line.name,
                               timestamp_ns=line.timestamp_ns)
            base = line.timestamp_ns * 1000
            evs = sorted((e for e in line.events
                          if w0 <= base + e.offset_ps < w1),
                         key=lambda e: e.offset_ps)
            if max_ops:
                evs = evs[:max_ops]
            for e in evs:
                ne = nl.events.add(metadata_id=e.metadata_id,
                                   offset_ps=e.offset_ps,
                                   duration_ps=e.duration_ps)
                _copy_stats(e, ne, plane, new, keep)
                used.add(e.metadata_id)
            if evs and max_ops and len(evs) == max_ops:
                cut = min(cut, base + evs[-1].offset_ps + evs[-1].duration_ps)
        for mid in used:
            md = plane.event_metadata[mid]
            nm = new.event_metadata[mid]
            nm.id = md.id
            nm.name = _name(md.name, all_stats)
            _copy_stats(md, nm, plane, new, keep)
    newh = out.planes.add(id=host.id, name=host.name)
    lines = {}
    for line, e in kept:
        start = line.timestamp_ns * 1000 + e.offset_ps
        if start >= cut:
            continue
        nl = lines.get(line.id)
        if nl is None:
            nl = lines[line.id] = newh.lines.add(
                id=line.id, name=line.name, timestamp_ns=line.timestamp_ns)
        nl.events.add(metadata_id=e.metadata_id, offset_ps=e.offset_ps,
                      duration_ps=min(e.duration_ps, cut - start))
        newh.event_metadata[e.metadata_id].id = e.metadata_id
        newh.event_metadata[e.metadata_id].name = \
            host.event_metadata[e.metadata_id].name
    with open(dst, "wb") as f:
        f.write(out.SerializeToString())


if __name__ == "__main__":
    args = [a for a in sys.argv[1:] if a != "--all-stats"]
    trim(args[0], args[1], *(int(a) for a in args[2:]),
         all_stats="--all-stats" in sys.argv)
