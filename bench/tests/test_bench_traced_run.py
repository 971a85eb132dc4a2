"""A ``--trace 1`` run past the chip: the profiler runs on the CPU, the
reduction is replaced by a fixed one, and every per-layer metric of the
cell must come out of its reader."""

from __future__ import annotations

import copy

from bench import run, trace
from bench.tests import tiny

CELL = "minicpm-2b.plan-prefill-1x2k"


def test_traced_plan_run_reports_every_layer_metric(monkeypatch):
    def fake(path, families=()):
        return {"window_s": 2.0, "busy_s": 1.5, "chips": 1,
                "families": {f: {"events": 3, "seconds": 0.5}
                             for f in families},
                "device_ops": [["fusion", 1.0]],
                "idle_gaps": [["bench.block", 0.5]]}
    monkeypatch.setattr(trace, "reduce", fake)
    ctx = copy.deepcopy(tiny.cell(CELL))
    ctx.traffic["trace_steps"] = 1
    device = dict(tiny.CPU, kind="TPU v5 lite")
    out = run.run_cell(ctx, tiny.SEED, 0.0, True, interpret=True,
                       device=device)
    wanted = {m["name"] for m in run.per_layer(ctx.bench, CELL)}
    assert set(out["metrics"]) == wanted
    assert out["metrics"]["idle_share.plan"]["value"] == 25.0
    assert out["device"]["busy_s"] == 1.5
    assert out["device"]["window_s"] == 2.0
    assert out["breakdown"]["idle_gaps"] == [["bench.block", 0.5]]
    assert out["correct"], out["check"]
    assert list(out)[-1] == "check"
