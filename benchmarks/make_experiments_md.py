"""Assemble EXPERIMENTS.md from the benchmarks' reports/ artifacts.
Narrative sections live in benchmarks/experiments_narrative.md and are
included verbatim.

    PYTHONPATH=src python -m benchmarks.make_experiments_md
"""

from __future__ import annotations

import json
import os


def bench_section() -> str:
    out = []
    path = "reports/benchmarks"
    def load(name):
        p = os.path.join(path, name + ".json")
        return json.load(open(p)) if os.path.exists(p) else None

    f4a = load("fig4a_model_accuracy")
    if f4a:
        out.append(f"**Fig 4(a) — analytical model accuracy:** "
                   f"{f4a['mean_accuracy']:.3f} mean over "
                   f"{f4a['n_points']} (layer, mapping) points "
                   f"(paper: 0.955).")
    f4b = load("fig4b_utilization_edp")
    if f4b:
        out.append(f"**Fig 4(b) — utilization/EDP trade-off "
                   f"({f4b['layer']}):** EDP reduction "
                   f"{f4b['edp_gain_vs_ws']:.2f}x vs WS, "
                   f"{f4b['edp_gain_vs_heuristic']:.2f}x vs heuristic.")
    f4c = load("fig4c_per_layer")
    if f4c:
        out.append(f"**Fig 4(c) — ResNet-18 network latency:** "
                   f"{f4c['speedup_vs_heuristic']:.2f}x vs heuristic, "
                   f"{f4c['speedup_vs_ws']:.2f}x vs WS (multiplicity-"
                   f"weighted sum over layers).")
    f5a = load("fig5a_models")
    if f5a:
        rats = ", ".join(f"{k} {v:.2f}x" for k, v in f5a["ratios"].items())
        out.append(f"**Fig 5(a) — EDP reduction across models** "
                   f"(paper: 1.6–3.2x): {rats}.")
    f5b = load("fig5bcd_hw_sweep")
    if f5b:
        rats = ", ".join(f"{k} {v:.2f}x" for k, v in f5b["ratios"].items())
        out.append(f"**Fig 5(b–d) — hardware robustness:** {rats}.")
    ff = load("tab_flexfact")
    if ff:
        out.append("**Flexible Factorization ablation** (conv4_x): see "
                   "`reports/benchmarks/tab_flexfact.json`.")
    tb = load("tpu_bridge")
    if tb:
        out.append("**TPU bridge (beyond paper):** MIP-selected Pallas "
                   "blocks per arch in `reports/benchmarks/tpu_bridge.json`"
                   f"; flash blocks @32k = {tb['flash_blocks_32k']}.")
    sched = load("sched_lm")
    if sched:
        lines = [
            f"**Network scheduler (beyond paper)** — serial-sum vs "
            f"weight-resident pipelined schedule, mode `{sched['mode']}`: "
            f"{sched['n_packed_rows']}/{len(sched['rows'])} (model, "
            f"scenario) rows packed >=1 segment; network-mode simulator "
            f"agreement {sched['mean_sim_accuracy']:.3f}.", "",
            "| model | scenario | segments | packed | serial cyc | "
            "sched cyc | speedup |",
            "|---|---|---|---|---|---|---|"]
        for r in sched["rows"]:
            lines.append(
                f"| {r['model']} | {r['scenario']} | {r['n_segments']} | "
                f"{r['n_packed']} | {r['serial_cycles']:.4g} | "
                f"{r['scheduled_cycles']:.4g} | {r['speedup']:.3f}x |")
        out.append("\n".join(lines))
    ex = load("exec_lm")
    if ex:
        rank = ex["pooled_rank_corr"]
        mode = "interpret mode" if ex["interpret"] else "compiled"
        rank_txt = f"{rank:.3f}" if rank is not None else "n/a"
        lines = [
            f"**Measured execution (beyond paper)** — optimized plans run "
            f"on the Pallas kernels ({mode}), kernels "
            f"{', '.join(ex['kernels'])}: pooled predicted-vs-measured "
            f"rank correlation {rank_txt} over {ex['n_rank_points']} "
            f"ops.", "",
            "| model | scenario | ops | pred serial cyc | measured ms | "
            "rank | max rel err | numerics |",
            "|---|---|---|---|---|---|---|---|"]
        for r in ex["rows"]:
            rr = f"{r['rank_corr']:.2f}" if r["rank_corr"] is not None \
                else "-"
            lines.append(
                f"| {r['model']} | {r['scenario']} | {r['ops']} | "
                f"{r['predicted_serial_cycles']:.4g} | "
                f"{r['measured_s'] * 1e3:.1f} | {rr} | "
                f"{r['max_rel_err']:.1e} | "
                f"{'ok' if r['numerics_ok'] else 'FAIL'} |")
        out.append("\n".join(lines))
    dse = load("dse_pareto")
    if dse:
        lines = [
            f"**Co-design DSE (beyond paper)** — workload "
            f"`{dse['workload']}`: screening pruned {dse['pruned']}/"
            f"{dse['grid']} archs ({100 * dse['prune_fraction']:.0f}%), "
            f"{len(dse['frontier'])} non-dominated survivors"
            f"{', all frontier mappings valid' if dse['frontier_validated'] else ' (INVALID mappings!)'}."
            " Frontier (ascending area):", "",
            "| arch | area bits | cycles | energy pJ | EDP |",
            "|---|---|---|---|---|"]
        for p in dse["frontier"]:
            lines.append(f"| {p['arch']} | {p['area_bits']:,} | "
                         f"{p['cycles']:.3g} | {p['energy_pj']:.3g} | "
                         f"{p['edp']:.4g} |")
        out.append("\n".join(lines))
    return "\n\n".join(out)


def main():
    narrative = ""
    np_path = "benchmarks/experiments_narrative.md"
    if os.path.exists(np_path):
        narrative = open(np_path).read()
    doc = f"""# EXPERIMENTS

{narrative}

## §Paper validation (deliverables b, d)

{bench_section()}
"""
    with open("EXPERIMENTS.md", "w") as f:
        f.write(doc)
    print("EXPERIMENTS.md written",
          f"({len(doc.splitlines())} lines)")


if __name__ == "__main__":
    main()
