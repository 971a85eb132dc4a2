"""Measured-execution backend (`core/executor.py`): lowering, dispatch,
numerics and the rank statistic, on tiny reduced workloads in Pallas
interpret mode (greedy solve mode — no MIP wall-clock in tier-1)."""

import math

import pytest

from repro.configs import get_config
from repro.configs.base import ShapeSpec
from repro.core import workload as wl
from repro.core.arch import default_arch
from repro.core.executor import ExecOp, execute_plan, lower_plan, spearman
from repro.core.frontend import extract_workload
from repro.core.network import optimize_network
from repro.core.tpu_bridge import VMEM_BYTES

ARCH = default_arch()
PREFILL = ShapeSpec("t_prefill", seq_len=64, global_batch=1, kind="prefill")
DECODE = ShapeSpec("t_decode", seq_len=64, global_batch=4, kind="decode")


def _net(cfg, spec):
    work = extract_workload(cfg, spec)
    return optimize_network(list(work.layers), ARCH, "greedy",
                            counts=list(work.counts), use_cache=False)


@pytest.fixture(scope="module")
def dense_prefill():
    cfg = get_config("minicpm-2b").reduced()
    return cfg, PREFILL, _net(cfg, PREFILL)


@pytest.fixture(scope="module")
def ssm_prefill():
    cfg = get_config("mamba2-1.3b").reduced()
    return cfg, PREFILL, _net(cfg, PREFILL)


# ---------------------------------------------------------------------------
# Rank statistic
# ---------------------------------------------------------------------------

def test_spearman_basics():
    assert spearman([1, 2, 3, 4], [10, 20, 30, 40]) == pytest.approx(1.0)
    assert spearman([1, 2, 3, 4], [40, 30, 20, 10]) == pytest.approx(-1.0)
    assert spearman([1, 2], [2, 1]) is None            # < 3 points
    assert spearman([1, 1, 1], [1, 2, 3]) is None      # constant side
    # monotone but nonlinear is still rank-1.0
    assert spearman([1, 2, 3, 4], [1, 10, 100, 1000]) == pytest.approx(1.0)


def test_spearman_ties_average_ranks():
    r = spearman([1, 2, 2, 3], [1, 2, 3, 4])
    assert r is not None and 0.8 < r < 1.0


# ---------------------------------------------------------------------------
# Op-kind tagging (frontend -> executor contract)
# ---------------------------------------------------------------------------

def test_frontend_layers_carry_op_kinds(dense_prefill, ssm_prefill):
    cfg, spec, _ = dense_prefill
    work = extract_workload(cfg, spec)
    kinds = {l.name.rpartition(".")[2]: l.op for l in work.layers}
    assert kinds["wq"] == wl.OP_ATTENTION
    assert kinds["wo"] == wl.OP_ATTENTION
    assert kinds["ffn_up"] == wl.OP_GEMM
    assert kinds["lm_head"] == wl.OP_GEMM
    cfg, spec, _ = ssm_prefill
    work = extract_workload(cfg, spec)
    kinds = {l.name.rpartition(".")[2]: l.op for l in work.layers}
    assert kinds["ssd_scores"] == wl.OP_SSD
    assert kinds["in_proj"] == wl.OP_GEMM


def test_layer_op_is_not_structural_identity():
    """Op tags route execution only: structurally identical layers dedup
    to one solve regardless of tag (cache keys ignore ``op``)."""
    from repro.core.cache import layer_cache_key
    a = wl.gemm("a", 64, 128, 256)
    b = wl.gemm("b", 64, 128, 256, op=wl.OP_ATTENTION)
    assert layer_cache_key(a) == layer_cache_key(b)


# ---------------------------------------------------------------------------
# Lowering
# ---------------------------------------------------------------------------

def test_lower_plan_dense_prefill(dense_prefill):
    cfg, spec, net = dense_prefill
    plan = lower_plan(cfg, spec, net, ARCH)
    kernels = [op.kernel for op in plan.ops]
    assert kernels.count("flash_attention") == 1
    assert "ssd_scan" not in kernels
    # one matmul op per workload layer (no layer dropped or duplicated)
    mm_idx = [i for op in plan.ops if op.kernel == "matmul_int8"
              for i in op.layer_indices]
    assert sorted(mm_idx) == list(range(len(net.layers)))
    # every matmul op carries its record's cycles and mapping-derived,
    # MXU-aligned blocks within the double-buffered VMEM budget, reducing
    # over the whole (lane-padded) K in one step wherever that fits
    for op in plan.ops:
        if op.kernel != "matmul_int8":
            continue
        lr = net.layers[op.layer_indices[0]]
        assert op.predicted_cycles == lr.record["cycles"]
        s = op.spec
        bm, bk, bn = s["bm"], s["bk"], s["bn"]
        assert bm % 8 == 0 and bk % 128 == 0 and bn % 128 == 0
        vmem = lambda bk: bm * bk + bk * bn + 4 * bm * bn
        assert 2 * vmem(bk) <= VMEM_BYTES
        kp = -(-s["k"] // 128) * 128
        if 2 * vmem(kp) <= VMEM_BYTES:
            assert bk == kp, (op.name, s)
    # prefill attention: causal square over the block's token dim
    fo = next(op for op in plan.ops if op.kernel == "flash_attention")
    assert fo.spec["causal"] and fo.spec["lq"] == fo.spec["lk"] == 64
    assert fo.predicted_cycles is None   # score stage is not a CIM layer


def test_lower_plan_decode_attention_uses_kv_cache(dense_prefill):
    cfg, _, _ = dense_prefill
    net = _net(cfg, DECODE)
    plan = lower_plan(cfg, DECODE, net, ARCH)
    fo = next(op for op in plan.ops if op.kernel == "flash_attention")
    assert not fo.spec["causal"]
    assert fo.spec["lq"] == 1                      # one step per sequence
    assert fo.spec["b"] == DECODE.global_batch     # sequences batch
    assert fo.spec["lk"] == DECODE.seq_len         # the cache

def test_lower_plan_fuses_ssd_intra_pair(ssm_prefill):
    cfg, spec, net = ssm_prefill
    plan = lower_plan(cfg, spec, net, ARCH)
    ssd = [op for op in plan.ops if op.kernel == "ssd_scan"]
    assert len(ssd) == 1
    (op,) = ssd
    i, j = op.layer_indices
    assert net.layers[i].layer.name.endswith("ssd_scores")
    assert net.layers[j].layer.name.endswith("ssd_y_intra")
    assert op.predicted_cycles == pytest.approx(
        net.layers[i].record["cycles"] + net.layers[j].record["cycles"])
    assert op.spec["n"] == cfg.ssm_state
    assert op.spec["p"] == cfg.ssm_head_dim
    # the remaining SSD state GEMMs dispatch to the matmul kernel
    names = {op2.name.rpartition(".")[2] for op2 in plan.ops
             if op2.kernel == "matmul_int8"}
    assert {"ssd_s_chunk", "ssd_y_inter"} <= names


def test_lower_plan_segments_follow_schedule(dense_prefill):
    cfg, spec, net = dense_prefill
    plan = lower_plan(cfg, spec, net, ARCH)
    ids = net.schedule.stage_segment_ids()
    assert len(ids) == len(net.layers)
    assert ids == sorted(ids)                      # segments are contiguous
    for op in plan.ops:
        assert op.segment == ids[op.layer_indices[0]]
    assert plan.n_segments == len(net.schedule.segments)


# ---------------------------------------------------------------------------
# Execution
# ---------------------------------------------------------------------------

def test_execute_plan_numerics_and_memoization(ssm_prefill):
    cfg, spec, net = ssm_prefill
    plan = lower_plan(cfg, spec, net, ARCH)
    rep = execute_plan(plan, interpret=True, repeats=1)
    assert rep.numerics_ok
    assert rep.max_rel_err < 1e-3
    assert rep.n_checked <= rep.n_ops              # structural memoization
    for op in plan.ops:
        assert op.measured_s is not None and op.measured_s > 0
        assert op.numerics_ok
    assert rep.measured_total_s == pytest.approx(
        sum(op.count * op.measured_s for op in plan.ops))
    pts = rep.rank_points()
    assert all(p > 0 and m > 0 for p, m in pts)
    assert len(pts) == len({op.key for op in plan.ops
                            if op.predicted_cycles is not None})


def test_execute_plan_deterministic_numerics(dense_prefill):
    """Same seed -> identical operands -> identical rel errors."""
    cfg, spec, net = dense_prefill
    p1 = lower_plan(cfg, spec, net, ARCH)
    p2 = lower_plan(cfg, spec, net, ARCH)
    execute_plan(p1, interpret=True, repeats=1, seed=3)
    execute_plan(p2, interpret=True, repeats=1, seed=3)
    for a, b in zip(p1.ops, p2.ops):
        assert a.rel_err == b.rel_err


def test_exec_op_key_structural():
    a = ExecOp("x", "matmul_int8", {"m": 8, "k": 128, "n": 128, "bm": 8,
                                    "bk": 128, "bn": 128}, 1, (0,))
    b = ExecOp("y", "matmul_int8", {"n": 128, "k": 128, "m": 8, "bn": 128,
                                    "bk": 128, "bm": 8}, 7, (3,))
    assert a.key == b.key                          # names/counts don't split


def test_gemm_mkn_roundtrip():
    from repro.core.executor import _gemm_mkn
    m, k, n = _gemm_mkn(wl.gemm("g", 5, 7, 11))
    assert (m, k, n) == (5, 11, 7)
    assert math.prod((m, k, n)) == wl.gemm("g", 5, 7, 11).macs
