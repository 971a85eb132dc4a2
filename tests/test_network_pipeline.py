"""Network-level pipeline (core/network.py) + library cache (core/cache.py):
dedup, global budget allocation, warm-start feasibility under time caps, and
cache key completeness / round-trips."""

import dataclasses
import math

import pytest

from repro.core.arch import default_arch
from repro.core.cache import (ResultCache, config_cache_key, layer_cache_key,
                              mapping_from_json, mapping_to_json,
                              solve_cached, solve_layer, solve_record_key)
from repro.core.formulation import FormulationConfig
from repro.core.mapping import validate
from repro.core.network import (allocate_budgets, dedup_layers,
                                optimize_network)
from repro.core.workload import conv, gemm

ARCH = default_arch()
TINY = gemm("tiny", 32, 64, 64)


# ---------------------------------------------------------------------------
# Dedup
# ---------------------------------------------------------------------------

def test_dedup_structural_identity():
    a = gemm("block0.ffn", 64, 128, 256)
    b = gemm("block7.ffn", 64, 128, 256)       # same shape, different name
    c = gemm("other", 64, 128, 512)
    unique, keys = dedup_layers([a, b, c])
    assert [l.name for l in unique] == ["block0.ffn", "other"]
    assert keys[0] == keys[1] != keys[2]
    assert layer_cache_key(a) == layer_cache_key(b)


def test_dedup_respects_stride():
    a = conv("x", 1, 8, 8, 4, 4, 3, 3, stride=1)
    b = conv("y", 1, 8, 8, 4, 4, 3, 3, stride=2)
    assert layer_cache_key(a) != layer_cache_key(b)


def test_two_identical_layers_one_solve_shared_mapping():
    a = gemm("l0", 32, 64, 64)
    b = gemm("l5", 32, 64, 64)
    res = optimize_network([a, b], ARCH, "greedy", use_cache=False)
    assert res.n_unique == 1 and res.n_solved == 1
    r0, r1 = res.layers[0].record, res.layers[1].record
    # shared mapping, re-scored per layer: identical numbers, own names
    assert r0["mapping"] == r1["mapping"]
    assert r0["cycles"] == r1["cycles"] and r0["edp"] == r1["edp"]
    assert r0["layer"] == "l0" and r1["layer"] == "l5"
    mp = mapping_from_json(r0["mapping"])
    assert not validate(mp, a, ARCH) and not validate(mp, b, ARCH)


def test_counts_scale_aggregates():
    a = gemm("a", 32, 64, 64)
    res1 = optimize_network([a], ARCH, "greedy", use_cache=False)
    res4 = optimize_network([a], ARCH, "greedy", counts=[4],
                            use_cache=False)
    assert res4.totals["cycles"] == pytest.approx(4 * res1.totals["cycles"])
    assert res4.totals["edp"] == pytest.approx(4 * res1.totals["edp"])


# ---------------------------------------------------------------------------
# Budget allocation
# ---------------------------------------------------------------------------

LAYERS = [gemm("big", 512, 512, 512), gemm("mid", 128, 128, 128),
          gemm("small", 8, 8, 8)]


def test_budgets_sum_to_global_budget():
    for total in (12.0, 30.0, 100.0, 7.0):
        b = allocate_budgets(LAYERS, total, min_s=2.0, max_s=60.0)
        assert sum(b) == pytest.approx(total)
    # floors + weighted remainder still sum exactly
    b = allocate_budgets(LAYERS, 20.0, min_s=5.0, max_s=60.0)
    assert sum(b) == pytest.approx(20.0)
    assert b[2] == pytest.approx(5.0)          # tiny layer pinned to floor


def test_budgets_weighted_by_macs_and_clamped():
    b = allocate_budgets(LAYERS, 30.0, min_s=2.0, max_s=20.0)
    assert b[0] >= b[1] >= b[2] >= 2.0
    assert max(b) <= 20.0
    # below the affordable floor: even split, sum preserved
    b = allocate_budgets(LAYERS, 3.0, min_s=2.0, max_s=20.0)
    assert b == [1.0, 1.0, 1.0]
    # above all caps: everyone capped (sum intentionally < total)
    b = allocate_budgets(LAYERS, 1000.0, min_s=2.0, max_s=20.0)
    assert b == [20.0, 20.0, 20.0]
    assert allocate_budgets([], 10.0) == []


# ---------------------------------------------------------------------------
# Warm start under time caps
# ---------------------------------------------------------------------------

def test_time_capped_mip_always_returns_feasible_mapping():
    # a cap far below what the solver needs: the greedy/heuristic incumbent
    # must come back as the mapping (never None)
    res = optimize_network([TINY], ARCH, "miredo", per_layer_cap_s=0.2,
                           use_cache=False, workers=1)
    rec = res.layers[0].record
    assert rec["mapping"] is not None
    mp = mapping_from_json(rec["mapping"])
    assert not validate(mp, TINY, ARCH)
    assert math.isfinite(rec["cycles"]) and rec["cycles"] > 0


def test_solve_layer_ws_time_capped_feasible():
    cfg = FormulationConfig(time_limit_s=0.2)
    rec = solve_layer(TINY, ARCH, "ws", cfg)
    assert rec["mapping"] is not None and rec["status"]


# ---------------------------------------------------------------------------
# Cache
# ---------------------------------------------------------------------------

def test_cache_round_trip_equals_fresh_solve(tmp_path):
    cache = ResultCache(str(tmp_path))
    cfg = FormulationConfig(time_limit_s=1.0)
    fresh = solve_layer(TINY, ARCH, "greedy", cfg)
    first = solve_cached(TINY, ARCH, "greedy", cfg, cache=cache)
    again = solve_cached(TINY, ARCH, "greedy", cfg, cache=cache)
    assert first == again                     # served from disk
    for k in ("cycles", "energy_pj", "edp", "mapping", "status"):
        assert first[k] == fresh[k], k
    # mapping JSON round-trips to the identical Mapping
    mp = mapping_from_json(first["mapping"])
    assert mapping_to_json(mp) == first["mapping"]


def test_pipeline_cache_hits(tmp_path):
    cache = ResultCache(str(tmp_path))
    layers = [gemm("a", 32, 64, 64), gemm("b", 32, 64, 64),
              gemm("c", 16, 64, 64)]
    r1 = optimize_network(layers, ARCH, "greedy", cache=cache)
    assert (r1.n_solved, r1.cache_hits) == (2, 0)
    r2 = optimize_network(layers, ARCH, "greedy", cache=cache)
    assert (r2.n_solved, r2.cache_hits) == (0, 2)
    assert r2.totals == r1.totals


def test_cache_key_covers_all_config_fields():
    """The seed's key ignored mu1/mu2_frac/latency_slack/mip_rel_gap/
    combo_cap — changing objective weights silently returned stale
    mappings. Every result-affecting field must now change the key."""
    base = FormulationConfig()
    for field, value in [
        ("alpha", 0.5), ("k_min", 2), ("mu1", 2.0), ("mu2_frac", 0.1),
        ("time_limit_s", 10.0), ("mip_rel_gap", 0.2), ("combo_cap", 999),
        ("latency_slack", 4.0), ("weight_stationary", True),
    ]:
        changed = dataclasses.replace(base, **{field: value})
        assert config_cache_key(changed) != config_cache_key(base), field
        assert solve_record_key("miredo", TINY, ARCH, changed) != \
            solve_record_key("miredo", TINY, ARCH, base), field
    # verbose has no effect on the result -> same key
    assert config_cache_key(dataclasses.replace(base, verbose=True)) == \
        config_cache_key(base)


def test_cache_key_canonicalizes_aliased_latency_slack():
    """``latency_slack`` values at or below BIG_M_FLOOR all build the same
    big-M (``max(slack, floor) * UB``) — they are result-aliased, so they
    must digest to ONE cache key; values above the floor stay distinct."""
    from repro.core.formulation import BIG_M_FLOOR
    base = FormulationConfig()          # default slack == 8.0, above floor
    at_floor = dataclasses.replace(base, latency_slack=BIG_M_FLOOR)
    below = dataclasses.replace(base, latency_slack=1.0)
    lower = dataclasses.replace(base, latency_slack=2.0)
    assert config_cache_key(at_floor) == config_cache_key(below) == \
        config_cache_key(lower)
    assert config_cache_key(base) != config_cache_key(at_floor)
    assert solve_record_key("miredo", TINY, ARCH, below) == \
        solve_record_key("miredo", TINY, ARCH, at_floor)
    assert solve_record_key("miredo", TINY, ARCH, base) != \
        solve_record_key("miredo", TINY, ARCH, at_floor)


def test_baseline_mode_keys_ignore_solver_budget():
    """Heuristic/greedy solves don't consume the MIP budget: their cache
    keys must not change with it (else every benchmark budget re-runs the
    same 2000-sample searches)."""
    a = FormulationConfig(time_limit_s=60.0)
    b = dataclasses.replace(a, time_limit_s=45.0, mu1=2.0,
                            latency_slack=4.0)
    for mode in ("heuristic", "greedy", "random"):
        assert solve_record_key(mode, TINY, ARCH, a) == \
            solve_record_key(mode, TINY, ARCH, b), mode
    # ...but factorization knobs still matter for the sampled searches
    c = dataclasses.replace(a, alpha=0.9)
    assert solve_record_key("heuristic", TINY, ARCH, c) != \
        solve_record_key("heuristic", TINY, ARCH, a)
    # and MIP modes keep budget sensitivity
    assert solve_record_key("miredo", TINY, ARCH, a) != \
        solve_record_key("miredo", TINY, ARCH, b)


def test_stale_cache_not_served_across_configs(tmp_path):
    cache = ResultCache(str(tmp_path))
    a = FormulationConfig(time_limit_s=1.0)
    b = dataclasses.replace(a, mu1=3.0)       # objective weight changed
    cache.put(solve_record_key("miredo", TINY, ARCH, a), {"stub": 1})
    assert cache.get(solve_record_key("miredo", TINY, ARCH, a)) is not None
    assert cache.get(solve_record_key("miredo", TINY, ARCH, b)) is None


def test_solver_never_imports_jax():
    """A spawned solver worker imports only the solver: one MIP solve in a
    fresh interpreter must leave JAX unloaded, so no worker can load the
    TPU library while its parent owns the chip."""
    import os
    import subprocess
    import sys

    code = (
        "import sys\n"
        "from repro.core.arch import default_arch\n"
        "from repro.core.formulation import FormulationConfig\n"
        "from repro.core.network import _solve_job\n"
        "from repro.core.workload import gemm\n"
        "rec = _solve_job((gemm('g', 16, 64, 64), default_arch(), 'miredo',"
        " FormulationConfig(time_limit_s=1.0)))\n"
        "assert rec['mapping'], rec\n"
        "print('jax' in sys.modules)\n")
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120,
                         env={**os.environ, "PYTHONPATH": src})
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip().splitlines()[-1] == "False"
