"""Benchmark harness entry point: ``PYTHONPATH=src python -m benchmarks.run``

One benchmark per paper table/figure, plus the beyond-paper jobs: the TPU
bridge, the ``lm`` job (the whole LM model zoo lowered through the model
frontend, ``benchmarks/lm_models.py``), the ``dse`` job (hardware/
dataflow co-design Pareto frontier, ``benchmarks/dse_pareto.py``), the
``sched`` job (serial-sum vs multi-core-scheduled end-to-end latency,
``benchmarks/sched_lm.py``), the ``serve`` job (request-level serving
under traffic with continuous batching, ``benchmarks/serve_sim.py``) and
the ``exec`` job (optimized plans executed on the Pallas kernels,
predicted vs measured, ``benchmarks/exec_lm.py``), the ``mesh`` job
(multi-chip mesh scaling with TP sharding and (chip, core) placement,
``benchmarks/mesh_scaling.py``) and the ``train`` job (training
workloads: backward-pass + optimizer-step lowering with per-model
fwd/bwd/update splits, ``benchmarks/train_lm_workloads.py``).
``--quick`` trims solve budgets; results cache under reports/cache so
reruns are incremental, and ``--cache-dir`` points the solve-record cache
at a persistent location shared across runs/machines (equivalent to
setting ``MIREDO_CACHE``). Unknown ``--only`` names fail the run — a typo
must not produce an empty, green harness.
"""

from __future__ import annotations

import argparse
import os
import time
import traceback


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--reduced", action="store_true",
                    help="CPU smoke-test reductions + acceptance gates for "
                         "the jobs that support them (implies --quick)")
    ap.add_argument("--only", default="",
                    help="comma list: fig4a,fig4b,fig4c,fig5a,fig5bcd,"
                         "flexfact,bridge,lm,dse,sched,serve,exec,optspeed,"
                         "mesh,train")
    ap.add_argument("--cache-dir", default="",
                    help="persistent solve-record cache directory (sets "
                         "MIREDO_CACHE; default reports/cache)")
    ap.add_argument("--portfolio", action="store_true",
                    help="optspeed job only: run the racing-solver-"
                         "portfolio gate (incumbent-unimproved rate "
                         "before vs after at equal budget) instead of "
                         "the throughput race")
    args = ap.parse_args(argv)
    if args.reduced:
        args.quick = True
    if args.cache_dir:
        # Every ResultCache() resolves its directory through
        # cache.default_cache_dir(), which reads MIREDO_CACHE — setting it
        # here routes all jobs (including process-pool workers, which
        # inherit the environment) at the shared store.
        os.environ["MIREDO_CACHE"] = args.cache_dir
    budget = 20.0 if args.quick else 60.0
    only = set(filter(None, args.only.split(","))) if args.only else None

    from benchmarks import (dse_pareto, exec_lm, fig4a_model_accuracy,
                            fig4b_utilization_edp, fig4c_per_layer,
                            fig5a_models, fig5bcd_hw_sweep, lm_models,
                            mesh_scaling, opt_speed, sched_lm, serve_sim,
                            tab_flexfact, tpu_bridge_bench,
                            train_lm_workloads)

    jobs = [
        ("fig4a", lambda: fig4a_model_accuracy.run(
            budget_mappings=24 if args.quick else 60)),
        ("fig4b", lambda: fig4b_utilization_edp.run(budget_s=budget)),
        ("fig4c", lambda: fig4c_per_layer.run(budget_s=budget)),
        ("fig5a", lambda: fig5a_models.run(budget_s=budget,
                                           quick=args.quick)),
        ("fig5bcd", lambda: fig5bcd_hw_sweep.run(
            budget_s=budget, quick=args.quick)),
        ("flexfact", lambda: tab_flexfact.run(budget_s=min(budget, 45.0))),
        ("bridge", tpu_bridge_bench.run),
        ("lm", lambda: lm_models.run(budget_s=budget, quick=args.quick)),
        ("dse", lambda: dse_pareto.run(budget_s=budget, quick=args.quick,
                                       reduced=args.quick)),
        ("sched", lambda: sched_lm.run(budget_s=budget, quick=args.quick,
                                       reduced=args.quick)),
        # Request-level serving under traffic: continuous batching vs the
        # serial baseline, percentile latencies and SLO-goodput arch
        # ranking (benchmarks/serve_sim.py).
        ("serve", lambda: serve_sim.run(budget_s=budget, quick=args.quick,
                                        reduced=args.quick)),
        # exec always runs reduced and interpreted: interpret mode emulates
        # every grid step in Python, so full-size configs are a chip
        # exercise (benchmarks/exec_lm.py without --interpret), not a
        # harness target.
        ("exec", lambda: exec_lm.run(budget_s=budget, quick=args.quick,
                                     reduced=True, interpret=True)),
        # scalar-vs-batched throughput race + exact-agreement check; the
        # cold/warm DSE timing is its standalone --dse flag (minutes) and
        # the solver-portfolio gate its --portfolio flag.
        ("optspeed", lambda: opt_speed.run(quick=args.quick,
                                           portfolio=args.portfolio)),
        # Multi-chip mesh scaling: infeasible-on-one-chip model on 2-4
        # chips, TP sharding + (chip, core) placement
        # (benchmarks/mesh_scaling.py).
        ("mesh", lambda: mesh_scaling.run(budget_s=budget, quick=args.quick,
                                          reduced=args.reduced)),
        # Training workloads: backward-pass + optimizer-step lowering,
        # per-model fwd/dGrad/wGrad/update cycle splits and the layers
        # whose optimal backward dataflow differs from the forward's
        # (benchmarks/train_lm_workloads.py).
        ("train", lambda: train_lm_workloads.run(
            budget_s=budget, quick=args.quick, reduced=args.reduced)),
    ]
    # A typo'd --only used to run zero jobs and still print "All benchmarks
    # complete" with exit 0 — validate against the job list instead.
    known = {name for name, _ in jobs}
    if only is not None:
        unknown = only - known
        if unknown or not only:
            what = ", ".join(sorted(unknown)) if unknown else "(none given)"
            print(f"unknown --only job(s): {what}; "
                  f"known: {', '.join(name for name, _ in jobs)}")
            return 2
    failures = []
    for name, fn in jobs:
        if only and name not in only:
            continue
        print(f"\n{'='*70}\n== {name}\n{'='*70}", flush=True)
        t0 = time.monotonic()
        try:
            fn()
            print(f"[{name}] done in {time.monotonic()-t0:.0f}s", flush=True)
        except Exception:
            failures.append(name)
            traceback.print_exc()
    if failures:
        print(f"\nFAILED: {failures}")
        return 1
    print("\nAll benchmarks complete; JSON under reports/benchmarks/.")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
