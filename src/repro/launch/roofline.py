"""Roofline analysis from dry-run artifacts (EXPERIMENTS.md §Roofline).

Three terms per (arch × shape × mesh), all in seconds-per-step:

    compute    = HLO_FLOPs_per_device / PEAK_BF16_FLOPS
    memory     = HLO_bytes_per_device / HBM_BW
    collective = collective_bytes_per_device / ICI_BW_EFFECTIVE

plus MODEL_FLOPS (6·N·D dense / 6·N_active·D MoE) and the useful-compute
ratio MODEL_FLOPS / HLO_FLOPS (catches remat/padding/replication waste).
"""

from __future__ import annotations

import glob
import json
import os

from repro.chips import chip
from repro.configs import SHAPES, get_config

# the production meshes are v5e pods (launch/mesh.py)
_CHIP = chip()
PEAK_BF16_FLOPS = _CHIP.bf16_flops
HBM_BW = _CHIP.hbm_bw
ICI_BW = _CHIP.ici_bw
ICI_LINKS = _CHIP.ici_links


def model_flops(arch_id: str, shape_name: str) -> float:
    """Useful model FLOPs per step: 6·N_active·D (train) / 2·N_active·D
    (inference) for parameter matmuls, plus the sequence-mixer terms the
    6ND convention omits — causal-half attention score/value matmuls
    (2·B·L²·H·hd fwd) and SSD intra-chunk matmuls. 'Useful' credits only
    the causal half; full-L² HLO compute shows up as waste in
    useful_compute_ratio (motivating the flash kernel path)."""
    cfg = get_config(arch_id)
    shape = SHAPES[shape_name]
    n_active = cfg.active_param_count()
    b = shape.global_batch
    l = shape.seq_len
    tokens = b * (1 if shape.is_decode else l)
    train = shape.kind == "train"
    fb = 3.0 if train else 1.0           # fwd(+2x bwd)
    total = (6.0 if train else 2.0) * n_active * tokens
    hd = cfg.resolved_head_dim
    # attention mixer
    n_attn = 0
    if cfg.family in ("dense", "moe", "vlm"):
        n_attn = cfg.n_layers
    elif cfg.family == "encdec":
        n_attn = cfg.n_layers + cfg.encoder_layers
    elif cfg.family == "hybrid":
        n_attn = cfg.n_layers // max(cfg.attn_every, 1)
    if n_attn and cfg.n_heads:
        if shape.is_decode:
            total += fb * 4.0 * b * l * cfg.n_heads * hd * n_attn
        else:
            total += fb * 2.0 * b * l * l * cfg.n_heads * hd * n_attn
    # SSD mixer (intra-chunk scores + value matmuls, chunk=256)
    if cfg.family in ("ssm", "hybrid") and cfg.ssm_state:
        d_inner = cfg.ssm_expand * cfg.d_model
        n_h = d_inner // cfg.ssm_head_dim
        chunk = 256
        per_tok = 2.0 * chunk * n_h * (cfg.ssm_state + cfg.ssm_head_dim)
        if not shape.is_decode:
            total += fb * b * l * per_tok * cfg.n_layers
        else:
            total += fb * 2.0 * b * n_h * cfg.ssm_head_dim * \
                cfg.ssm_state * cfg.n_layers
    return total


def roofline_terms(rec: dict) -> dict:
    """rec: one dry-run JSON record (per-device quantities)."""
    if rec.get("status") != "ok":
        return {"status": rec.get("status", "missing"),
                "reason": rec.get("reason", rec.get("error", ""))}
    n_dev = 1
    for v in rec["mesh"].values():
        n_dev *= v
    flops = float(rec["flops_per_device"])
    mem_bytes = float(rec["bytes_per_device"])
    coll = rec.get("collective_bytes_per_device", {})
    # legacy records may hold negative per-kind extrapolations (one-time
    # collectives); clamp at zero
    coll = {k: max(v, 0.0) for k, v in coll.items()}
    coll_bytes = float(sum(coll.values()))
    t_compute = flops / PEAK_BF16_FLOPS
    t_memory = mem_bytes / HBM_BW
    t_coll = coll_bytes / (ICI_BW * ICI_LINKS)
    mflops = model_flops(rec["arch"], rec["shape"]) / n_dev
    dominant = max(("compute", t_compute), ("memory", t_memory),
                   ("collective", t_coll), key=lambda kv: kv[1])[0]
    bound = max(t_compute, t_memory, t_coll)
    return {
        "status": "ok",
        "n_devices": n_dev,
        "t_compute_s": t_compute,
        "t_memory_s": t_memory,
        "t_collective_s": t_coll,
        "dominant": dominant,
        "model_flops_per_device": mflops,
        "useful_compute_ratio": mflops / flops if flops > 0 else 0.0,
        "roofline_fraction": (mflops / PEAK_BF16_FLOPS) / bound
        if bound > 0 else 0.0,
        # CPU-backend memory_analysis: argument bytes are per-device, temp
        # bytes are summed across the module's devices (measured: see
        # DESIGN.md §Decisions) — divide temps by device count.
        "hbm_gb_per_device": (
            max(rec["memory"]["argument_bytes"], 0) +
            max(rec["memory"]["temp_bytes"], 0) / n_dev) / 1e9,
        "collective_breakdown": coll,
    }


def load_reports(report_dir: str) -> list[dict]:
    out = []
    for path in sorted(glob.glob(os.path.join(report_dir, "*.json"))):
        with open(path) as f:
            out.append(json.load(f))
    return out


def format_table(report_dir: str, multi_pod: bool = False) -> str:
    rows = []
    hdr = (f"| arch | shape | t_comp(ms) | t_mem(ms) | t_coll(ms) | "
           f"dominant | useful | roofline-frac | HBM GB/dev |")
    sep = "|" + "---|" * 9
    rows += [hdr, sep]
    for rec in load_reports(report_dir):
        if rec.get("multi_pod") != multi_pod:
            continue
        t = roofline_terms(rec)
        if t["status"] != "ok":
            rows.append(f"| {rec['arch']} | {rec['shape']} | - | - | - | "
                        f"{t['status']}: {t.get('reason','')[:40]} | - | - "
                        f"| - |")
            continue
        rows.append(
            f"| {rec['arch']} | {rec['shape']} | "
            f"{t['t_compute_s']*1e3:.2f} | {t['t_memory_s']*1e3:.2f} | "
            f"{t['t_collective_s']*1e3:.2f} | {t['dominant']} | "
            f"{t['useful_compute_ratio']:.2f} | "
            f"{t['roofline_fraction']:.3f} | "
            f"{t['hbm_gb_per_device']:.2f} |")
    return "\n".join(rows)


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--reports", default="reports/dryrun")
    ap.add_argument("--multi", action="store_true")
    a = ap.parse_args()
    print(format_table(a.reports, a.multi))
