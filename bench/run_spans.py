#!/usr/bin/env python3
"""One run of a cell as ``bench/run.py`` makes it, with the program's own
counters and, traced, its spans and scopes read from the same trace.

    python3 bench/run_spans.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1> [--keep-trace DIR]

``bench/run.py`` runs the cell unchanged: set-up, the window, the
reduction, every per-layer reader and the check of ``correct``. Around
its window this script keeps what ``repro.tracing``'s counters counted
(``jax.compiles`` among them: a compile in the window is a fault), and
with ``--trace 1`` it reads the trace with ``bench/spans.py`` before
``run.py`` deletes it, and raises if the window's ``matmul_int8.quantize``
spans do not match its ``matmul_int8.calls``. Standard error gets both
before ``run.py``'s check lines; the last line of standard output is
``run.py``'s result with ``"program"`` added: ``counted`` and, traced,
the ``spans.readings``, the ten longest idle gaps named by the innermost
span and ``kernel_lead_s``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import traceback

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import common, run, spans, trace  # noqa: E402


def counters() -> dict:
    """The program's counters; none where it has no ``repro.tracing``."""
    try:
        from repro import tracing
    except ImportError:
        return {}
    return tracing.counters()


def instrument(extra: dict) -> None:
    """Wrap the window and the reduction that ``run.run_cell`` calls."""
    run_window, reduce = common.run_window, trace.reduce

    def window(issue, seconds, **kw):
        before = counters()
        res = run_window(issue, seconds, **kw)
        extra["counted"] = {k: v - before.get(k, 0)
                            for k, v in sorted(counters().items())}
        common.log(f"[spans] counted in the window: "
                   f"{json.dumps(extra['counted'])}")
        return res

    def reduce_too(path, families=()):
        r = spans.reduce(path)
        spans.check_calls(r, extra.get("counted", {}))
        extra["readings"] = spans.readings(r)
        extra["idle_gaps"] = r["idle_gaps"]
        extra["kernel_lead_s"] = r["kernel_lead_s"]
        for key in ("spans", "idle_by_span", "scopes"):
            common.log(f"[spans] {key}: {json.dumps(r[key], sort_keys=True)}")
        common.log(f"[spans] readings: {json.dumps(extra['readings'])}, "
                   f"kernel_lead_s {r['kernel_lead_s']}")
        return reduce(path, families)

    common.run_window, trace.reduce = window, reduce_too


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None)
    args = ap.parse_args(argv)
    import jax
    extra: dict = {}
    instrument(extra)
    try:
        ctx = run.load_cell(ROOT, args.workload)
        device = run.check_device(ctx.cell["chips"])
        run._cache_on()
        # op_names are not in the compile cache's key by default: an
        # executable cached by a checkout whose ops carry other names
        # would run in place of this one's, and its scopes be read
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          True)
        out = run.run_cell(ctx, args.seed, args.seconds, bool(args.trace),
                           device=device, keep_trace=args.keep_trace)
    except run.NoChip as e:
        print(f"[spans] {e}", file=sys.stderr, flush=True)
        return 2
    except Exception as e:                      # no result line
        traceback.print_exc()
        print(f"[spans] FAILED: {type(e).__name__}: {e}", file=sys.stderr,
              flush=True)
        return 1
    out["program"] = extra
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
