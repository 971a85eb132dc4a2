"""Measured-execution backend: run an optimized plan on the Pallas kernels
and validate predicted vs measured (DESIGN.md §Executor).

Everything upstream of this module *predicts*: the MIP, the analytical
latency model and the event simulator agree with each other, but none of
them executes a kernel. This module closes that loop — the CIMFlow-style
execution+evaluation backend the ROADMAP's "runs as fast as the hardware
allows" demands:

  1. **Lowering.** A solved ``NetworkResult`` (plus its scheduler
     ``Schedule``) for one (model, scenario) pair is lowered to an
     ``ExecPlan``: every frontend layer, tagged with its op kind in
     `core/lm_workloads.py` (``workload.OP_GEMM`` / ``OP_ATTENTION`` /
     ``OP_SSD``), becomes an ``ExecOp`` dispatched to the kernel family
     that executes it —

       * weight GEMMs (projections, FFN/MoE mats, SSD state GEMMs, the LM
         head) -> `kernels/matmul_int8`, block shapes derived from the
         layer's *optimized mapping* by the TPU bridge
         (`tpu_bridge.select_blocks_from_mapping`): the mapping's M and N
         tiles and the whole reduction, as large as the double-buffered
         VMEM budget allows;
       * one score/AV stage per attention block -> `kernels/
         flash_attention` (`tpu_bridge.select_flash_blocks`); decode runs
         the step against a synthetic KV cache, prefill the full causal
         square. Score matmuls are deliberately *not* workload layers (they
         run on the attention unit, not the CIM macro — DESIGN.md §Model
         frontend), so these ops carry no predicted cycles and are excluded
         from the rank statistic, but are still timed and numerics-checked;
       * the SSD intra-chunk pair (scores + y_intra) -> fused
         `kernels/ssd_scan` invocation.

     Plan order is stream order, i.e. schedule order — each op is annotated
     with the segment that will execute it (`Schedule.stage_segment_ids`).
  2. **Execution.** Each structurally unique op runs once with warm-up plus
     timed repeats (operand *values* are synthetic; shapes, dtypes and
     block shapes are exactly the plan's). The kernels compile for the
     TPU; ``interpret=True`` runs them in the Pallas interpreter, which is
     how the tests drive the whole path on a CPU.
  3. **Validation.** Every kernel invocation is checked against its
     package's ``ref.py`` oracle (`quantized_matmul_and_ref`,
     `attention_ref`, `ssd_intra_chunk_and_ref`), and measured wall-clock
     is *ranked* against predicted cycles (`spearman`) — the Fig. 4(a)
     discipline, now model-vs-execution instead of model-vs-simulator.
     Absolute agreement is not expected (interpret-mode CPU seconds are not
     CIM cycles); monotonicity is: a layer the model calls heavier must
     measure heavier.

Entry points: ``execute_model`` (extract -> optimize -> lower -> execute),
``lower_plan`` / ``execute_plan`` for pre-solved results. Surfaced as the
``exec`` benchmark job (`benchmarks/exec_lm.py`) and wired into
`examples/serve_lm.py`'s served decode step. JAX/kernel imports stay
inside functions so MIP solves can still fan out across processes before
any kernel runs (fork-after-JAX deadlocks; see `examples/serve_lm.py`).
"""

from __future__ import annotations

import dataclasses
import math
import time
import zlib
from typing import Sequence

from repro.core import workload as wl
from repro.core.arch import CimArch
from repro.core.cache import mapping_from_json
from repro.core.tpu_bridge import select_blocks_from_mapping, \
    select_flash_blocks

#: Decode attention replays the step against a synthetic KV cache of the
#: scenario's sequence length, capped so interpret-mode CI stays fast (a
#: 32k-entry cache is a prediction-side scenario, not an execution target).
DECODE_KV_CAP = 512

#: Frobenius relative-error floor per kernel family vs its ref.py oracle.
#: matmul shares the oracle's int32 accumulation exactly (only the final
#: f32 scale multiply can round differently); attention/SSD re-associate
#: f32 reductions blockwise.
NUMERICS_TOL = {"matmul_int8": 1e-4, "flash_attention": 2e-3,
                "ssd_scan": 2e-3}


# ---------------------------------------------------------------------------
# Plan dataclasses
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ExecOp:
    """One kernel invocation of the plan (one or more workload layers)."""

    name: str
    kernel: str                    # matmul_int8 | flash_attention | ssd_scan
    spec: dict                     # kernel-family shape/block parameters
    count: int                     # network multiplicity (instances)
    layer_indices: tuple[int, ...]  # workload layers this op covers
    segment: int | None = None     # schedule segment executing this op
    #: Per-instance predicted cycles (sum of covered layers' records);
    #: ``None`` for ops with no workload layer (attention score stage).
    predicted_cycles: float | None = None
    measured_s: float | None = None        # per-invocation wall-clock
    #: Wall-clock of the numerics call: compiling the kernel and its oracle
    #: (on first use of the shape) plus one run of each.
    first_call_s: float | None = None
    rel_err: float | None = None           # vs the kernel's ref.py oracle
    numerics_ok: bool | None = None

    @property
    def key(self) -> tuple:
        """Structural execution identity: equal keys run identical kernels
        on identical shapes/blocks, so measurement and numerics memoize."""
        return (self.kernel,) + tuple(sorted(self.spec.items()))


@dataclasses.dataclass
class ExecPlan:
    model: str
    scenario: str
    arch_name: str
    ops: list[ExecOp]
    predicted_serial_cycles: float
    predicted_scheduled_cycles: float | None
    n_segments: int

    @property
    def n_unique(self) -> int:
        return len({op.key for op in self.ops})


@dataclasses.dataclass
class ExecReport:
    plan: ExecPlan
    #: Count-weighted measured wall-clock — the executed analogue of the
    #: serial-sum predicted cycles (unique ops run once; instances scale).
    measured_total_s: float
    #: Spearman rank correlation of per-op predicted cycles vs measured
    #: seconds over the plan's unique predicted ops (None under 3 points).
    rank_corr: float | None
    numerics_ok: bool
    max_rel_err: float
    n_ops: int
    n_unique: int
    n_checked: int

    def rank_points(self) -> list[tuple[float, float]]:
        """(predicted cycles, measured seconds) per unique predicted op —
        poolable across reports for a fleet-level rank statistic."""
        seen, pts = set(), []
        for op in self.plan.ops:
            if op.predicted_cycles is None or op.measured_s is None or \
                    op.key in seen:
                continue
            seen.add(op.key)
            pts.append((op.predicted_cycles, op.measured_s))
        return pts


# ---------------------------------------------------------------------------
# Rank statistic
# ---------------------------------------------------------------------------

def spearman(xs: Sequence[float], ys: Sequence[float]) -> float | None:
    """Spearman rank correlation (scipy, average ranks for ties); ``None``
    when fewer than 3 points or either side is constant."""
    from scipy.stats import spearmanr
    assert len(xs) == len(ys)
    if len(xs) < 3 or len(set(xs)) == 1 or len(set(ys)) == 1:
        return None
    rho = float(spearmanr(xs, ys)[0])
    return None if math.isnan(rho) else rho


# ---------------------------------------------------------------------------
# Lowering: NetworkResult -> ExecPlan
# ---------------------------------------------------------------------------

def _gemm_mkn(layer: wl.Layer) -> tuple[int, int, int]:
    """GEMM-speak (M x K) @ (K x N) from the canonical loop nest."""
    assert layer.is_gemm, layer.name
    return layer.bound("N"), layer.bound("C"), layer.bound("K")


def _matmul_op(idx: int, lr, arch: CimArch) -> ExecOp:
    m, k, n = _gemm_mkn(lr.layer)
    mapping = mapping_from_json(lr.record["mapping"])
    c = select_blocks_from_mapping(mapping, lr.layer, arch)
    return ExecOp(
        name=lr.layer.name, kernel="matmul_int8",
        spec={"m": m, "k": k, "n": n, "bm": c.bm, "bk": c.bk, "bn": c.bn},
        count=lr.count, layer_indices=(idx,),
        predicted_cycles=lr.record["cycles"])


def _flash_op(prefix: str, group: dict, cfg, spec) -> ExecOp | None:
    """The score/AV stage of one attention block (no workload layer — no
    predicted cycles; see module docstring)."""
    if "wq" not in group:
        return None
    qi, qlr = group["wq"]
    lq = qlr.layer.bound("N")
    h, hd = cfg.n_heads, cfg.resolved_head_dim
    idxs = tuple(i for i, _ in group.values())
    if spec.is_decode:
        # one decode step against the (synthetic) KV cache: every cached
        # position is visible, sequences batch on the leading dim. The
        # cache is the decoder's own stream for self-attention; cached
        # cross-attention (kv_m=0 — no wk/wv at decode) attends the
        # encoder memory instead.
        cache = (cfg.frontend_seq or spec.seq_len) \
            if prefix.endswith(".xattn") else spec.seq_len
        lk = min(int(cache), DECODE_KV_CAP)
        b, lq, causal = lq, 1, False
    elif "wk" not in group:
        return None                 # defensive: prefill group without K/V
    else:
        lk = group["wk"][1].layer.bound("N")
        # cross-attention and the encoder's bidirectional self-attention
        # (the frontend's `.xattn` / `.enc` groups) see every position;
        # decoder/self streams are causal
        bidi = prefix.endswith(".xattn") or prefix.endswith(".enc")
        b, causal = 1, not bidi
    bq, bk = select_flash_blocks(lq, lk, hd)
    return ExecOp(
        name=f"{prefix}.attention", kernel="flash_attention",
        spec={"b": b, "lq": lq, "lk": lk, "h": h, "hd": hd,
              "causal": causal, "bq": bq, "bk": bk},
        count=qlr.count, layer_indices=idxs)


def lower_plan(cfg, spec, net, arch: CimArch) -> ExecPlan:
    """Lower a solved ``NetworkResult`` for ``(cfg, spec)`` into an
    executable plan. ``net.layers`` must be the workload extracted by
    `frontend.extract_workload(cfg, spec)` in order (op-kind tags intact).
    """
    layers = net.layers
    seg_ids = net.schedule.stage_segment_ids() if net.schedule else None
    ops: list[ExecOp] = []
    i = 0
    while i < len(layers):
        lr = layers[i]
        kind = lr.layer.op
        prefix, _, leaf = lr.layer.name.rpartition(".")
        if kind == wl.OP_ATTENTION:
            # contiguous projection run of one block: wq/wo[/wk/wv]
            group: dict[str, tuple[int, object]] = {}
            j = i
            while j < len(layers) and layers[j].layer.op == wl.OP_ATTENTION \
                    and layers[j].layer.name.rpartition(".")[0] == prefix:
                group[layers[j].layer.name.rpartition(".")[2]] = \
                    (j, layers[j])
                ops.append(_matmul_op(j, layers[j], arch))
                j += 1
            fo = _flash_op(prefix, group, cfg, spec)
            if fo is not None:
                ops.append(fo)
            i = j
            continue
        if kind == wl.OP_SSD and leaf == "ssd_scores" and \
                i + 1 < len(layers) and \
                layers[i + 1].layer.name == f"{prefix}.ssd_y_intra":
            # fused intra-chunk pair: scores (C B^T) + y_intra (scores X)
            sc, yi = lr, layers[i + 1]
            assert sc.count == yi.count, (sc.count, yi.count)
            q = sc.layer.bound("N")
            ops.append(ExecOp(
                name=f"{prefix}.ssd_intra", kernel="ssd_scan",
                spec={"q": q, "n": sc.layer.bound("C"),
                      "p": yi.layer.bound("K")},
                count=sc.count, layer_indices=(i, i + 1),
                predicted_cycles=sc.record["cycles"] + yi.record["cycles"]))
            i += 2
            continue
        # plain weight GEMM (FFN/MoE/LM head/projections) or SSD state GEMM
        ops.append(_matmul_op(i, lr, arch))
        i += 1
    if seg_ids is not None:
        for op in ops:
            op.segment = seg_ids[op.layer_indices[0]]
    sched = net.scheduled
    return ExecPlan(
        model=cfg.name, scenario=spec.name, arch_name=net.arch_name,
        ops=ops, predicted_serial_cycles=net.totals["cycles"],
        predicted_scheduled_cycles=sched["cycles"] if sched else None,
        n_segments=len(net.schedule.segments) if net.schedule else 0)


# ---------------------------------------------------------------------------
# Execution
# ---------------------------------------------------------------------------

def _rel_err(out, ref) -> float:
    import numpy as np
    a = np.asarray(out, np.float32)
    b = np.asarray(ref, np.float32)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-12))


def _first_call(fn):
    """``(fn(), seconds)`` for the first, compiling call of ``fn``."""
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def _time_call(fn, warmup: int, repeats: int) -> float:
    """min-of-repeats wall-clock of ``fn()`` after ``warmup`` extra calls.
    Callers count their numerics invocation as the first warm-up (it
    already paid jit tracing), so they pass ``warmup - 1``."""
    import jax
    for _ in range(max(warmup, 0)):
        jax.block_until_ready(fn())
    best = math.inf
    for _ in range(max(repeats, 1)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def _run_matmul(op: ExecOp, rng, interpret: bool, warmup: int,
                repeats: int) -> tuple[float, float, float]:
    import jax.numpy as jnp
    from repro.kernels.matmul_int8.ops import (quantized_matmul,
                                               quantized_matmul_and_ref)
    s = op.spec
    x = jnp.asarray(rng.standard_normal((s["m"], s["k"])), jnp.float32)
    w = jnp.asarray(rng.standard_normal((s["k"], s["n"])) * 0.1,
                    jnp.float32)
    blocks = (s["bm"], s["bk"], s["bn"])
    (out, ref), first = _first_call(lambda: quantized_matmul_and_ref(
        x, w, block_shapes=blocks, interpret=interpret))
    t = _time_call(
        lambda: quantized_matmul(x, w, block_shapes=blocks,
                                 interpret=interpret,
                                 out_dtype=jnp.float32),
        warmup - 1, repeats)
    return first, t, _rel_err(out, ref)


def _run_flash(op: ExecOp, rng, interpret: bool, warmup: int,
               repeats: int) -> tuple[float, float, float]:
    import jax.numpy as jnp
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import attention_ref
    s = op.spec
    mk = lambda l: jnp.asarray(
        rng.standard_normal((s["b"], l, s["h"], s["hd"])), jnp.float32)
    q, k, v = mk(s["lq"]), mk(s["lk"]), mk(s["lk"])
    call = lambda: flash_attention(q, k, v, causal=s["causal"],
                                   block_q=s["bq"], block_k=s["bk"],
                                   interpret=interpret)
    (out, ref), first = _first_call(
        lambda: (call(), attention_ref(q, k, v, causal=s["causal"])))
    return first, _time_call(call, warmup - 1, repeats), _rel_err(out, ref)


def _run_ssd(op: ExecOp, rng, interpret: bool, warmup: int,
             repeats: int) -> tuple[float, float, float]:
    import jax.numpy as jnp
    from repro.kernels.ssd_scan.ops import (ssd_intra_chunk,
                                            ssd_intra_chunk_and_ref)
    s = op.spec
    q, n, p = s["q"], s["n"], s["p"]
    c = jnp.asarray(rng.standard_normal((1, 1, q, 1, n)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((1, 1, q, 1, n)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.001, 0.1, (1, 1, q, 1)), jnp.float32)
    a = -jnp.asarray(rng.uniform(0.5, 4.0, (1,)), jnp.float32)
    ss = jnp.cumsum(dt * a, axis=2)
    x = jnp.asarray(rng.standard_normal((1, 1, q, 1, p)), jnp.float32)
    (out, ref), first = _first_call(lambda: ssd_intra_chunk_and_ref(
        c, b, ss, dt, x, interpret=interpret))
    t = _time_call(
        lambda: ssd_intra_chunk(c, b, ss, dt, x, interpret=interpret),
        warmup - 1, repeats)
    return first, t, _rel_err(out, ref)


_RUNNERS = {"matmul_int8": _run_matmul, "flash_attention": _run_flash,
            "ssd_scan": _run_ssd}


def execute_plan(plan: ExecPlan, *, interpret: bool = False, warmup: int = 1,
                 repeats: int = 2, seed: int = 0, verbose: bool = False,
                 memo: dict | None = None) -> ExecReport:
    """Execute every structurally unique op of ``plan`` (memoized by
    ``ExecOp.key``) with warm-up + timed repeats, numerics-check each kernel
    against its ``ref.py`` oracle, and fill the per-op measurement fields
    in place. Deterministic for a fixed ``seed``.

    ``memo`` can be shared across plans executed with identical
    (interpret, warmup, repeats, seed) settings — reduced configs
    deliberately share shapes across models, and a structurally identical
    op measures once (`benchmarks/exec_lm.py`)."""
    import numpy as np

    memo = {} if memo is None else memo
    for op in plan.ops:
        if op.key not in memo:
            # crc32 over the structural key: stable across processes
            # (tuple hash() is salted), so reruns rebuild identical operands
            rng = np.random.default_rng(
                [seed, zlib.crc32(repr(op.key).encode())])
            memo[op.key] = _RUNNERS[op.kernel](op, rng, interpret, warmup,
                                               repeats)
            if verbose:
                f, t, e = memo[op.key]
                print(f"[exec] {op.kernel:>16} {op.name}: {t * 1e3:.2f} ms "
                      f"rel_err {e:.2e} (first call {f:.2f} s)")
        op.first_call_s, op.measured_s, op.rel_err = memo[op.key]
        op.numerics_ok = op.rel_err <= NUMERICS_TOL[op.kernel]
    report = ExecReport(
        plan=plan,
        measured_total_s=sum(op.count * op.measured_s for op in plan.ops),
        rank_corr=None, numerics_ok=all(op.numerics_ok for op in plan.ops),
        max_rel_err=max(op.rel_err for op in plan.ops),
        n_ops=len(plan.ops), n_unique=plan.n_unique,
        n_checked=len({op.key for op in plan.ops}))
    pts = report.rank_points()
    report.rank_corr = spearman([p for p, _ in pts], [m for _, m in pts])
    return report


def execute_model(cfg, spec, arch: CimArch | None = None, *,
                  mode: str = "miredo", per_layer_cap_s: float = 2.0,
                  total_budget_s: float | None = None,
                  workers: int | None = 1, net=None,
                  interpret: bool = False, warmup: int = 1, repeats: int = 2,
                  seed: int = 0, verbose: bool = False) -> ExecReport:
    """Extract -> optimize -> lower -> execute for one (model, scenario).

    ``net`` short-circuits the solve with a pre-computed ``NetworkResult``
    for exactly this workload (e.g. `examples/serve_lm.py`, which already
    optimized the served decode step). ``workers`` defaults to 1: kernels
    import JAX, and forking a solver pool afterwards risks deadlock."""
    from repro.core.arch import default_arch
    from repro.core.frontend import extract_workload
    from repro.core.network import optimize_network

    arch = arch or default_arch()
    if net is None:
        work = extract_workload(cfg, spec)
        net = optimize_network(list(work.layers), arch, mode,
                               counts=list(work.counts),
                               per_layer_cap_s=per_layer_cap_s,
                               total_budget_s=total_budget_s,
                               workers=workers, verbose=verbose)
    plan = lower_plan(cfg, spec, net, arch)
    return execute_plan(plan, interpret=interpret, warmup=warmup,
                        repeats=repeats, seed=seed, verbose=verbose)
