"""Cells cut to a size a CPU test can run: the harness's whole run past
its look for a chip, the Pallas kernels in the interpreter."""

from __future__ import annotations

import copy
import os

from bench import run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
SEED = 3_000_000_123

SMALL = {"n_layers": 3, "d_model": 64, "n_heads": 4, "n_kv_heads": 4,
         "head_dim": 16, "d_ff": 128, "vocab_size": 256}
DENSE = {"n_layers": 2, "d_model": 128, "n_heads": 2, "n_kv_heads": 2,
         "head_dim": 64, "d_ff": 256, "vocab_size": 512}

#: (model, traffic, limits) of each cell cut down. The limits at this
#: size lie between readings at this size over five seeds (CPU,
#: interpret mode): decode gap 0.002 to 0.038 sound, 0.28 to 0.73 in
#: float8; prefill logits_rel 0.012 to 0.018 sound, 0.13 to 0.21 in
#: float8; plan matmul_err 0.042 to 0.048 sound, 0.76 to 0.90 in int4.
#: The CPU runs HIGH as HIGHEST, so attention_err cannot separate here
#: and keeps the cell's own limit.
CUTS = {
    "minicpm-2b.decode-b8-1k": (SMALL, {
        "batch": 4, "prompt": 64, "turn": 8, "prefill_batch": 2,
        "check_sequences": 4}, {"gap": 0.12}),
    "minicpm-2b.prefill-1x2k": (SMALL, {
        "prompt": 64, "prompts": 4, "check_prompts": 4},
        {"logits_rel": 0.06}),
    "minicpm-2b.plan-prefill-1x2k": (DENSE, {
        "seq_len": 128, "mode": "greedy"},
        {"matmul_err": 0.3, "attention_err": 1e-4}),
}


def cell(name: str):
    model, traffic, limits = CUTS[name]
    ctx = run.load_cell(ROOT, name)
    ctx.config["model"].update(model)
    ctx.traffic.update(traffic)
    ctx.limits = dict(limits)
    return ctx


def run_tiny(name: str, *, control: bool = False, seed: int = SEED) -> dict:
    return run.run_cell(copy.deepcopy(cell(name)), seed, 0.05, False,
                        control=control, interpret=True, device=CPU)
