#!/usr/bin/env python3
"""Run one cell of the benchmark on the chip this process holds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name: the cell in ``BENCHMARK.json``
names its configuration (``bench/configs/<config>.json``) and its traffic
(``bench/traffic/<traffic>.json``, whose ``path`` names the module in
``bench/paths/``); its limits are in ``bench/workloads/<cell>.json`` and
each per-layer metric's reader in ``bench/metrics/<metric>.py``.

A run makes its weights and inputs from ``--seed``, warms up every shape
(set-up), and then either measures for ``--seconds`` (``--trace 0``: the
cell's end-to-end metrics) or traces ``trace_steps`` whole steps with the
profiler (``--trace 1``: its per-layer metrics). Then it frees the
program's state and compares what the window produced with the plain
reference. The last line of standard output is one JSON object; the
numbers compared are the last lines of standard error.

A run that finds no TPU, or fewer chips than the cell asks for, exits 2
and prints no result. ``--control 1`` puts the reference, one precision
step lower, in the program's place: it must come out not correct.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()                 # set-up is counted from here

import argparse                          # noqa: E402
import glob                              # noqa: E402
import importlib                         # noqa: E402
import importlib.util                    # noqa: E402
import json                              # noqa: E402
import os                                # noqa: E402
import shutil                            # noqa: E402
import sys                               # noqa: E402
import tempfile                          # noqa: E402
import traceback                         # noqa: E402
import types                             # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)


class NoChip(RuntimeError):
    pass


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(root: str, name: str) -> types.SimpleNamespace:
    """The cell ``name`` with everything it names, read from ``root``."""
    bench = _json(os.path.join(root, "BENCHMARK.json"))
    cells = {c["name"]: c for c in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no cell {name!r} in BENCHMARK.json "
                       f"(cells: {sorted(cells)})")
    cell = cells[name]
    d = os.path.join(root, "bench")
    return types.SimpleNamespace(
        root=root, bench=bench, cell=cell,
        config=_json(os.path.join(d, "configs", cell["config"] + ".json")),
        traffic=_json(os.path.join(d, "traffic", cell["traffic"] + ".json")),
        limits=_json(os.path.join(d, "workloads", name + ".json"))["limits"])


def end_to_end(bench: dict, cell: str) -> list[dict]:
    return [m for m in bench["end_to_end"]
            if cell in m.get("workloads", [cell])]


def per_layer(bench: dict, cell: str) -> list[dict]:
    """The per-layer metrics whose ``workloads`` list this cell."""
    return [m for m in bench["per_layer"] if cell in m["workloads"]]


def reader(root: str, metric: str):
    path = os.path.join(root, "bench", "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def check_device(chips: int) -> dict:
    """The device as JAX reports it; NoChip unless it is a TPU with at
    least ``chips`` chips and a row in the peaks table."""
    import jax

    from bench.peaks import peaks
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found "
                     f"{len(devs)}")
    peaks(devs[0].device_kind)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _cache_on() -> None:
    """JAX's persistent compilation cache in the checkout, for every
    program, so that only a checkout's first run compiles."""
    import jax

    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def _trace_window(path, steps: int, in_flight: int, families,
                  keep: str | None = None) -> tuple[dict, dict]:
    """Trace ``steps`` whole steps; (window dict, reduced trace). The raw
    trace is deleted unless ``keep`` names a directory for it."""
    import jax

    from bench import common, trace
    tmp = keep or tempfile.mkdtemp(prefix="bench-trace-")
    try:
        jax.profiler.start_trace(tmp)
        try:
            with common.span("bench.window"):
                res = common.run_window(path.issue, 0.0, in_flight=in_flight,
                                        min_steps=steps, max_steps=steps)
        finally:
            jax.profiler.stop_trace()
        files = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                          recursive=True)
        if not files:
            raise RuntimeError("the profiler wrote no trace")
        return res, trace.reduce(files[0], families)
    finally:
        if not keep:
            shutil.rmtree(tmp, ignore_errors=True)


def run_cell(ctx: types.SimpleNamespace, seed: int, seconds: float,
             trace: bool, *, control: bool = False, interpret: bool = False,
             device: dict | None = None, keep_trace: str | None = None
             ) -> dict:
    """One run of a loaded cell; returns the result object. ``device`` is
    what ``check_device`` found (a test passes its own)."""
    from bench import common
    cell = ctx.cell["name"]
    ctx.seed, ctx.interpret = int(seed), interpret
    mod = importlib.import_module(f"bench.paths.{ctx.traffic['path']}")
    path = mod.setup(ctx)
    setup_s = time.perf_counter() - T0
    common.log(f"[run] {cell}: set-up {setup_s:.3f} s")
    metrics, breakdown = {}, None
    if not trace:
        res = common.run_window(path.issue, seconds,
                                in_flight=ctx.traffic["in_flight"])
        path.after_window()
        for m in end_to_end(ctx.bench, cell):
            if m["name"] == "setup_s":
                value = setup_s
            elif m["name"] == mod.METRIC:
                value = res["seconds"] / res["steps"] * 1e3
            else:
                continue
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        common.log(f"[run] window {res['seconds']:.4f} s, {res['steps']} "
                   f"steps")
    else:
        from bench.peaks import peaks
        work = path.work()
        families = sorted(work["kernels"])
        res, red = _trace_window(path, ctx.traffic["trace_steps"],
                                 ctx.traffic["in_flight"], families,
                                 keep_trace)
        path.after_window()
        rec = dict(red, steps=res["steps"], work=work,
                   peaks=peaks(device["kind"]))
        for m in per_layer(ctx.bench, cell):
            value = reader(ctx.root, m["name"])(rec)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device = dict(device, busy_s=red["busy_s"], window_s=red["window_s"])
        breakdown = {"device_ops": red["device_ops"],
                     "idle_gaps": red["idle_gaps"]}
        common.log(f"[run] traced {res['steps']} steps: window "
                   f"{red['window_s']:.6f} s, busy {red['busy_s']:.6f} s, "
                   f"families {red['families']}")
    device = dict(device, memory_peak_bytes=common.memory_peak_bytes())
    numbers = path.numbers(control=control)
    over = [k for k, (v, lim) in numbers.items() if not v <= lim]
    out = {"correct": not over, "attempted": res["steps"],
           "failed": len(over), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["check"] = {k: {"value": v, "limit": lim}
                    for k, (v, lim) in numbers.items()}
    for k, (v, lim) in numbers.items():
        common.log(f"check {k} {v!r} limit {lim!r} "
                   f"{'ok' if k not in over else 'OVER'}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="keep the raw trace of --trace 1 in this directory")
    args = ap.parse_args(argv)
    try:
        ctx = load_cell(ROOT, args.workload)
        device = check_device(ctx.cell["chips"])
        print(f"[run] JAX holds the chip {time.perf_counter() - T0:.3f} s "
              f"into set-up", file=sys.stderr, flush=True)
        _cache_on()
        out = run_cell(ctx, args.seed, args.seconds, bool(args.trace),
                       control=bool(args.control), device=device,
                       keep_trace=args.keep_trace)
    except NoChip as e:
        print(f"[run] {e}", file=sys.stderr, flush=True)
        return 2
    except Exception as e:                      # no result line
        traceback.print_exc()
        print(f"[run] FAILED: {type(e).__name__}: {e}", file=sys.stderr,
              flush=True)
        return 1
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
