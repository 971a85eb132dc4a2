"""Collectives' share of the device's busy time: the device seconds of
the collective instruction families the path names (``COLLECTIVES`` of
``bench/paths/serve_decode_tp.py``, counted on every chip) over the
busy seconds of all the chips that ran anything."""

from bench.paths.serve_decode_tp import COLLECTIVES


def read(rec: dict) -> float | None:
    named = [f for f in (rec["work"].get("kernels") or {})
             if f in COLLECTIVES]
    if not named:
        return None
    seen = [rec["families"][f] for f in named if f in rec["families"]]
    if not any(s["events"] for s in seen):
        raise RuntimeError(f"the path names the collectives {named} but "
                           f"the trace has no event of them")
    return 100.0 * sum(s["seconds"] for s in seen) / (
        rec["busy_s"] * rec["chips"])
