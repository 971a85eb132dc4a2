"""Trim a trace recorded by ``bench/run.py --trace 1 --keep-trace DIR`` into
the small fixture the reducer's test reads.

    python bench/tests/trim_trace.py <xplane.pb> <out.xplane.pb> [max_ops]

Keeps the ``/host:CPU`` plane's ``bench.*`` spans and, of every TPU
plane, the ``XLA Ops`` line: its first ``max_ops`` events inside the
``bench.window`` span (which is then cut to end with the last of them),
and of each event's metadata only what the reducer reads: the HLO
instruction's name, and the op's kind where it is a control-flow op
(``%while.2 = ... while(...)``). Needs TensorFlow's xplane protobuf,
which the tests themselves do not.
"""

from __future__ import annotations

import sys

CONTAINERS = (" while(", " conditional(", " call(")


def short(name: str) -> str:
    """An op event's name cut to what the reducer reads."""
    head = name.split(" = ", 1)[0]
    kind = [k for k in CONTAINERS if k in name]
    return head + (f" = ...{kind[0]}...)" if kind else "")


def trim(src: str, dst: str, max_ops: int = 4000) -> None:
    from tensorflow.tsl.profiler.protobuf import xplane_pb2
    space = xplane_pb2.XSpace()
    with open(src, "rb") as f:
        space.ParseFromString(f.read())
    host = next(p for p in space.planes if p.name == "/host:CPU")
    spans = []
    for line in host.lines:
        keep = [e for e in line.events
                if host.event_metadata[e.metadata_id].name.startswith(
                    "bench.")]
        spans += [(line, e) for e in keep]
    win = [(ln, e) for ln, e in spans
           if host.event_metadata[e.metadata_id].name == "bench.window"][0]
    w0 = win[0].timestamp_ns * 1000 + win[1].offset_ps
    w1 = w0 + win[1].duration_ps
    cut = w1
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
                         key=lambda e: e.offset_ps)[:max_ops]
            for e in evs:
                nl.events.add(metadata_id=e.metadata_id, offset_ps=e.offset_ps,
                              duration_ps=e.duration_ps)
                used.add(e.metadata_id)
            if evs and len(evs) == max_ops:
                cut = min(cut, base + evs[-1].offset_ps + evs[-1].duration_ps)
        for mid in used:
            md = plane.event_metadata[mid]
            new.event_metadata[mid].id = md.id
            new.event_metadata[mid].name = short(md.name)
    newh = out.planes.add(id=host.id, name=host.name)
    lines = {}
    for line, e in spans:
        nl = lines.get(line.id)
        if nl is None:
            nl = lines[line.id] = newh.lines.add(
                id=line.id, name=line.name, timestamp_ns=line.timestamp_ns)
        start = line.timestamp_ns * 1000 + e.offset_ps
        if start >= cut:
            continue
        dur = min(e.duration_ps, cut - start)
        nl.events.add(metadata_id=e.metadata_id, offset_ps=e.offset_ps,
                      duration_ps=dur)
        md = host.event_metadata[e.metadata_id]
        newh.event_metadata[e.metadata_id].id = md.id
        newh.event_metadata[e.metadata_id].name = md.name
    with open(dst, "wb") as f:
        f.write(out.SerializeToString())


if __name__ == "__main__":
    trim(sys.argv[1], sys.argv[2], *(int(a) for a in sys.argv[3:]))
