"""Optimizer throughput benchmark (the ``optspeed`` job): scalar vs
batched analytical model, plus the persistent-cache DSE speedup.

Three measurements, one JSON row (``reports/benchmarks/opt_speed.json``):

  1. **mappings/sec** on sampler pools (one GEMM, one conv): the historical
     per-candidate scalar loop (``mapping.validate`` +
     ``energy.evaluate_edp``) against the batched scorer
     (`latency_batched.score_mappings`). Before
     timing, the batched scores are checked for *exact* equality with the
     scalar loop on every feasible row (infeasible rows must come back
     ``inf``) — a speedup that changes answers is a bug, not a result.
  2. the same race on a **feasible-only** pool, isolating evaluation
     throughput from the sampler's ~90% capacity-infeasible candidates
     (which the scalar loop rejects cheaply in ``validate``).
  3. optionally (``--dse``): a cold then warm ``dse --reduced`` run against
     a fresh persistent cache directory — the warm run must reproduce the
     cold frontier byte-for-byte and beat its wall clock by
     ``DSE_MIN_SPEEDUP``x (the ISSUE-6 acceptance number).

The throughput gate (used by the CI optspeed-smoke job) requires the best
batched/scalar ratio across pools to reach ``MIN_RATIO`` — timings use
best-of-``REPEATS`` to shrug off scheduler noise on small CI boxes.

    PYTHONPATH=src python benchmarks/opt_speed.py --quick
    PYTHONPATH=src python benchmarks/opt_speed.py --dse
"""

from __future__ import annotations

import argparse
import math
import os
import random
import sys
import time

if __package__ in (None, ""):      # `python benchmarks/opt_speed.py`
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from benchmarks.common import md_table, write_report
from repro.core import latency_batched as lb
from repro.core import workload as wl
from repro.core.arch import default_arch
from repro.core.baselines import sample_mapping_raw
from repro.core.energy import evaluate_edp
from repro.core.factorization import factorize_layer_dims
from repro.core.mapping import validate

#: Throughput gate: best batched/scalar ratio across pools. 1.0
#: ("no slower than the loop it replaced") — measured margins are
#: 1.2-1.3x on the feasible-only pool, but a single shared CI core is
#: noisy, so the gate asserts parity and the JSON records the margin.
MIN_RATIO = 1.0
#: Cold/warm wall-clock ratio the persistent-cache DSE rerun must reach.
DSE_MIN_SPEEDUP = 5.0
#: Best-of-N timing repeats.
REPEATS = 3

#: ``--portfolio`` mode: per-layer budget for both the single-solve
#: baseline pass and the racing-portfolio pass (equal total budget — the
#: ISSUE-10 gate condition). 3 s sits where the fine model misses its
#: first integer point on the hard reduced-zoo layers but the coarse
#: portfolio member's slice still lands one.
PORTFOLIO_BUDGET_S = 3.0
#: Wall-clock tolerance on the per-layer budget contract (process
#: scheduling + one formulation build that straddles the deadline).
PORTFOLIO_EPS_S = 0.75
#: Reduced LM zoo for the portfolio gate: two decode workloads with
#: structurally diverse GEMMs (attention/FFN/head + Mamba SSD).
PORTFOLIO_MODELS = ("minicpm-2b", "mamba2-1.3b")
PORTFOLIO_SCENARIOS = ("decode_32k",)


def _pools(quick: bool) -> list[tuple[str, object, int]]:
    """(name, layer, pool size): one GEMM and one conv."""
    n = 512 if quick else 2000
    return [
        ("gemm", wl.gemm("g", 32, 512, 512), n),
        ("conv", wl.conv("c", 1, 64, 64, 28, 28, 3, 3), n),
    ]


def _sample_pool(layer, arch, n: int, seed: int = 0) -> list:
    rng = random.Random(seed)
    factors = factorize_layer_dims({d: layer.bound(d) for d in wl.DIMS})
    return [sample_mapping_raw(layer, arch, rng, factors)
            for _ in range(n)]


def _scalar_scores(pool, layer, arch) -> list[tuple[float, float, float]]:
    """The historical per-candidate loop: validate, then full EDP."""
    out = []
    for mp in pool:
        if validate(mp, layer, arch):
            out.append((math.inf, math.inf, math.inf))
        else:
            e = evaluate_edp(mp, layer, arch)
            out.append((e.latency.total_cycles, e.energy.total_pj, e.edp))
    return out


def _best_of(fn, repeats: int = REPEATS) -> float:
    best = math.inf
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def _check_agreement(pool, layer, arch, name: str) -> int:
    """Exact scalar/batched equality on every row; returns feasible count."""
    ref = _scalar_scores(pool, layer, arch)
    sc = lb.score_mappings(pool, layer, arch)
    for i, (cyc, pj, edp) in enumerate(ref):
        got = (float(sc.cycles[i]), float(sc.energy_pj[i]), float(sc.edp[i]))
        if got != (cyc, pj, edp):
            raise RuntimeError(
                f"[optspeed] {name} row {i}: batched {got} "
                f"!= scalar {(cyc, pj, edp)}")
    return sum(r[0] != math.inf for r in ref)


def _race(pool, layer, arch) -> dict[str, float]:
    """Best-of-N wall seconds per contender on one pool."""
    need = ("feasible", "latency", "energy")
    return {"scalar": _best_of(lambda: _scalar_scores(pool, layer, arch)),
            "batched": _best_of(lambda: lb.score_mappings(
                pool, layer, arch, need=need))}


def _dse_cold_warm(cache_dir: str) -> dict:
    """Cold vs warm ``dse --reduced`` against one persistent cache dir."""
    from benchmarks import dse_pareto

    def frontier(payload):
        return [(p["arch"], p["cycles"], p["energy_pj"], p["area_bits"])
                for p in payload["frontier"]]

    prev = os.environ.get("MIREDO_CACHE")
    os.environ["MIREDO_CACHE"] = cache_dir
    try:
        t0 = time.perf_counter()
        cold = dse_pareto.run(reduced=True)
        cold_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        warm = dse_pareto.run(reduced=True)
        warm_s = time.perf_counter() - t0
    finally:
        if prev is None:
            os.environ.pop("MIREDO_CACHE", None)
        else:
            os.environ["MIREDO_CACHE"] = prev
    if frontier(cold) != frontier(warm):
        raise RuntimeError(
            f"[optspeed] warm DSE rerun changed the frontier:\n"
            f"cold: {frontier(cold)}\nwarm: {frontier(warm)}")
    speedup = cold_s / max(warm_s, 1e-9)
    if speedup < DSE_MIN_SPEEDUP:
        raise RuntimeError(
            f"[optspeed] persistent-cache DSE rerun only {speedup:.1f}x "
            f"faster (acceptance: >={DSE_MIN_SPEEDUP:g}x)")
    return {"cold_s": round(cold_s, 2), "warm_s": round(warm_s, 2),
            "speedup": round(speedup, 1),
            "frontier_identical": True,
            "frontier_archs": [p["arch"] for p in cold["frontier"]]}


def _portfolio_layers():
    """Unique layers of the reduced portfolio zoo, first-seen order."""
    from repro.configs import get_config
    from repro.core.frontend import extract_all
    from repro.core.network import dedup_layers

    pool = []
    for aid in PORTFOLIO_MODELS:
        cfg = get_config(aid).reduced()
        for work in extract_all(cfg, PORTFOLIO_SCENARIOS).values():
            pool.extend(work.layers)
    unique, _ = dedup_layers(pool)
    return unique


def _portfolio_bench(budget_s: float = PORTFOLIO_BUDGET_S) -> dict:
    """``--portfolio``: incumbent-unimproved rate, single solve vs racing
    portfolio at equal per-layer budget (the ISSUE-10 tentpole gate).

    Per unique reduced-zoo layer:

      * **before** — one single-parameterization ``optimize_layer`` at
        ``budget_s``;
      * **after** — ``portfolio.race`` of the default K=3 grid at the same
        ``budget_s``, seeded with the before-pass mapping (the portfolio's
        incumbent-sharing mechanism), which makes "never worse than the
        single solve" hold *by construction*;
      * the race runs twice with identical seeds as a determinism probe.

    Gates (RuntimeError on violation):

      1. the unimproved rate (fraction of layers where the returned
         mapping is not strictly better than the *native* greedy/heuristic
         incumbent) strictly drops from before to after;
      2. no layer's after-latency exceeds its before-latency;
      3. every solve's wall clock stays within ``budget_s`` +
         ``PORTFOLIO_EPS_S`` (the post-ladder-fix budget contract);
      4. for layers whose winning member terminated deterministically
         (OPTIMAL / INFEASIBLE — not at the wall-clock wire), both race
         passes return bit-identical (winner, latency, mapping). Members
         cut off by the clock are deterministic only up to machine load —
         the *selection rule* is a pure function of member results either
         way (DESIGN.md §Solver portfolio).
    """
    from repro.core.cache import mapping_to_json
    from repro.core.formulation import FormulationConfig, optimize_layer
    from repro.core.portfolio import default_portfolio, race

    arch = default_arch()
    fc = FormulationConfig(time_limit_s=budget_s)
    pf = default_portfolio()
    unique = _portfolio_layers()
    print(f"[optspeed/portfolio] {len(unique)} unique layers, "
          f"{budget_s:g}s/layer, grid "
          f"{[m.name for m in pf.members]} (digest {pf.digest()})")

    rows, layers_json = [], []
    n_before = n_after = 0
    budget_violations, worse, nondet = [], [], []
    for ul in unique:
        before = optimize_layer(ul, arch, fc)
        out = race(ul, arch, fc, pf, warm_start=before.mapping)
        out2 = race(ul, arch, fc, pf, warm_start=before.mapping)
        after = out.result
        n_before += before.improved
        n_after += after.improved
        if after.eval_latency > before.eval_latency:
            worse.append(ul.name)
        for tag, s in (("single", before.solve_seconds),
                       ("portfolio", after.solve_seconds),
                       ("portfolio-rerun", out2.result.solve_seconds)):
            if s > budget_s + PORTFOLIO_EPS_S:
                budget_violations.append(f"{ul.name}/{tag}: {s:.2f}s")
        w1, w2 = out.members[out.winner], out2.members[out2.winner]
        det_eligible = {w1.status, w2.status} <= {"OPTIMAL", "INFEASIBLE"}
        det_same = (out.winner == out2.winner and
                    out.result.eval_latency == out2.result.eval_latency and
                    mapping_to_json(out.result.mapping) ==
                    mapping_to_json(out2.result.mapping))
        if det_eligible and not det_same:
            nondet.append(ul.name)
        rows.append([ul.name, f"{before.incumbent_latency:.0f}",
                     f"{before.eval_latency:.0f}", int(before.improved),
                     f"{after.eval_latency:.0f}", int(after.improved),
                     out.members[out.winner].name])
        layers_json.append({
            "layer": ul.name,
            "incumbent_cycles": before.incumbent_latency,
            "before_cycles": before.eval_latency,
            "before_improved": before.improved,
            "before_s": round(before.solve_seconds, 2),
            "after_cycles": after.eval_latency,
            "after_improved": after.improved,
            "after_s": round(after.solve_seconds, 2),
            "winner": out.winner,
            "winner_name": out.members[out.winner].name,
            "deterministic_rerun": det_same,
            "members": out.to_json()["members"],
        })

    n = len(unique)
    rate_before = 1.0 - n_before / n
    rate_after = 1.0 - n_after / n
    print(md_table(["layer", "incumbent", "single", "imp",
                    "portfolio", "imp", "winner"], rows))
    print(f"[optspeed/portfolio] incumbent-unimproved rate: "
          f"{rate_before:.3f} -> {rate_after:.3f} "
          f"(gate: strict drop at equal {budget_s:g}s/layer budget)")
    if worse:
        raise RuntimeError(
            f"[optspeed/portfolio] portfolio worse than single solve on: "
            f"{worse}")
    if budget_violations:
        raise RuntimeError(
            f"[optspeed/portfolio] budget contract violated "
            f"(> {budget_s:g}+{PORTFOLIO_EPS_S:g}s): {budget_violations}")
    if nondet:
        raise RuntimeError(
            f"[optspeed/portfolio] deterministically-terminated winners "
            f"changed between identical-seed reruns on: {nondet}")
    if not rate_after < rate_before:
        raise RuntimeError(
            f"[optspeed/portfolio] incumbent-unimproved rate did not "
            f"strictly drop: {rate_before:.3f} -> {rate_after:.3f}")
    return {"budget_s": budget_s, "eps_s": PORTFOLIO_EPS_S,
            "models": list(PORTFOLIO_MODELS),
            "scenarios": list(PORTFOLIO_SCENARIOS),
            "grid": [m.name for m in pf.members],
            "digest": pf.digest(),
            "n_layers": n,
            "rate_before": round(rate_before, 4),
            "rate_after": round(rate_after, 4),
            "layers": layers_json}


def run(budget_s: float = 0.0, quick: bool = False, dse: bool = False,
        portfolio: bool = False, cache_dir: str | None = None) -> dict:
    """``budget_s`` is accepted for harness uniformity; the pools are
    fixed-size so the job's cost is set by ``quick`` and ``dse``.
    ``portfolio=True`` runs ONLY the solver-portfolio gate
    (`_portfolio_bench`) — its zoo is already the reduced one, so
    ``--reduced``/``--quick`` change nothing for it."""
    if portfolio:
        payload = {"portfolio": _portfolio_bench()}
        write_report("opt_speed_portfolio", payload)
        return payload
    arch = default_arch()
    rows, pools_json = [], {}
    best_ratio, best_where = 0.0, ""
    for name, layer, n in _pools(quick):
        pool = _sample_pool(layer, arch, n)
        feas = _check_agreement(pool[: min(n, 256)], layer, arch, name)
        # feasible-only variant: evaluation throughput without the
        # sampler's capacity-infeasible majority
        fpool = [mp for mp in pool if not validate(mp, layer, arch)]
        for tag, p in ((name, pool), (f"{name}-feasible", fpool)):
            if not p:
                continue
            t = _race(p, layer, arch)
            entry = {"pool": len(p), "scalar_s": round(t["scalar"], 4)}
            for k, v in t.items():
                if k == "scalar":
                    continue
                ratio = t["scalar"] / v
                entry[k.replace("-", "_") + "_s"] = round(v, 4)
                entry[k.replace("-", "_") + "_ratio"] = round(ratio, 3)
                if ratio > best_ratio:
                    best_ratio, best_where = ratio, f"{tag}/{k}"
                rows.append([tag, k, len(p),
                             round(len(p) / t["scalar"]),
                             round(len(p) / v), f"{ratio:.2f}x"])
            pools_json[tag] = entry
        print(f"[optspeed] {name}: agreement exact on "
              f"{min(n, 256)} rows ({feas} feasible)")

    print(md_table(["pool", "scorer", "n", "scalar maps/s",
                    "batched maps/s", "ratio"], rows))
    print(f"[optspeed] best batched/scalar ratio {best_ratio:.2f}x "
          f"({best_where}); gate >={MIN_RATIO:g}x")
    if best_ratio < MIN_RATIO:
        raise RuntimeError(
            f"[optspeed] batched scorer slower than scalar everywhere "
            f"(best {best_ratio:.2f}x < {MIN_RATIO:g}x)")

    payload = {"quick": quick,
               "agreement": "exact", "pools": pools_json,
               "best_ratio": round(best_ratio, 3),
               "best_ratio_pool": best_where}
    if dse:
        import tempfile
        cd = cache_dir or tempfile.mkdtemp(prefix="optspeed-cache-")
        print(f"[optspeed] cold/warm dse --reduced, cache {cd}")
        payload["dse"] = _dse_cold_warm(cd)
        print(f"[optspeed] dse cold {payload['dse']['cold_s']}s -> warm "
              f"{payload['dse']['warm_s']}s "
              f"({payload['dse']['speedup']}x, frontier identical)")
    write_report("opt_speed", payload)
    return payload


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true",
                    help="smaller pools (CI smoke size)")
    ap.add_argument("--dse", action="store_true",
                    help="also time cold vs warm dse --reduced against a "
                         "persistent cache (minutes, not seconds)")
    ap.add_argument("--cache-dir", default=None,
                    help="persistent cache dir for --dse (default: fresh "
                         "temp dir, i.e. a true cold start)")
    ap.add_argument("--portfolio", action="store_true",
                    help="run only the racing-solver-portfolio gate: "
                         "incumbent-unimproved rate before vs after on "
                         "the reduced LM zoo at equal per-layer budget")
    args = ap.parse_args(argv)
    run(quick=args.quick, dse=args.dse, portfolio=args.portfolio,
        cache_dir=args.cache_dir)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
