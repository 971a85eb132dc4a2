"""MIREDO -> TPU bridge: the paper's MIP machinery re-instantiated over the
TPU memory hierarchy (HBM -> VMEM -> MXU) to select Pallas kernel block
shapes (DESIGN.md §TPU bridge).

The CIM concepts map one-to-one:
  * eq. (9)  capacity with (1 + psi^DM):  Pallas pipelining double-buffers
    every operand block in VMEM -> working set counts twice when the
    transfer/compute overlap is enabled;
  * Table III single vs double rows:  per-grid-step time is
    max(T_transfer, T_compute) when pipelined, T_transfer + T_compute when
    not;
  * C^X spatial legality:  MXU tiling — lane dim multiples of 128, sublane
    multiples of 8;
  * weight-reload mode-switch stall:  the weight block changes every grid
    step along the reduction axis; re-fetch traffic is modeled in the HBM
    term exactly like MIREDO models macro reloads.

The resulting MIP is tiny (tens of binaries) and solves in milliseconds —
it is deliberately *not* routed through the network pipeline or its solve
cache (those key on `workload.Layer` x `CimArch`; a block-shape pick is
neither). Call paths today: ``select_matmul_blocks`` feeds
kernels/matmul_int8 (ops zero-pad operands when a padded block comes
back), ``select_flash_blocks`` feeds kernels/flash_attention, and
``benchmarks/tpu_bridge_bench.py`` sweeps both for the report.
"""

from __future__ import annotations

import dataclasses
import math

from repro.chips import chip
from repro.core.mip.model import LinExpr, MipModel, Status

_CHIP = chip()
#: VMEM budget for the blocks eq. 9 counts: half of one core's VMEM (the
#: kernels request more, `chips.VMEM_LIMIT_BYTES`).
VMEM_BYTES = _CHIP.vmem_bytes // 2
HBM_BW = _CHIP.hbm_bw
MXU_FLOPS = _CHIP.bf16_flops       # bf16; int8 ~2x but stay conservative
LANE = 128
SUBLANE = 8


@dataclasses.dataclass
class BlockChoice:
    bm: int
    bk: int
    bn: int
    double_buffered: bool
    est_seconds: float
    vmem_bytes: int
    status: str


def _round_up(x: int, align: int) -> int:
    return -(-x // align) * align


def _candidates(dim: int, *, align: int, cap: int) -> list[int]:
    """MXU-legal block-size candidates for one dim: aligned divisors of the
    dim when any exist, else the dim padded up to alignment (clamped to an
    aligned cap). Every returned candidate is a multiple of ``align`` — an
    unaligned block shape is illegal for the MXU regardless of fit."""
    out = [c for c in (128, 256, 512, 1024, 2048)
           if c <= min(dim, cap) and dim % c == 0 and c % align == 0]
    if not out and dim % align == 0 and align <= dim <= cap:
        out = [dim]                       # aligned dim smaller than 128
    if not out:
        # no aligned divisor exists: offer every aligned size up to the dim
        # padded to alignment (clamped to an aligned cap) — callers
        # (kernels/matmul_int8/ops.py) zero-pad the array to the block
        padded = min(_round_up(dim, align), max(align, cap - cap % align))
        out = [c for c in (128, 256, 512, 1024, 2048)
               if c % align == 0 and c <= padded]
        if padded not in out:
            out.append(padded)
    return out


def select_matmul_blocks(m: int, k: int, n: int, *,
                         bytes_in: int = 1, bytes_acc: int = 4,
                         vmem_bytes: int = VMEM_BYTES,
                         time_limit_s: float = 5.0) -> BlockChoice:
    """MIP block-shape selection for the INT8 matmul kernel.

    min  T              (per-step latency bound, eq. 14 latency term)
    s.t. T >= t_hbm     (+ t_mxu when single-buffered: Table III row select)
         T >= t_mxu
         (1 + psi^DM) * working_set(bm, bk, bn) <= VMEM     (eq. 9)
    """
    cm = _candidates(m, align=SUBLANE, cap=2048)
    ck = _candidates(k, align=LANE, cap=2048)
    cn = _candidates(n, align=LANE, cap=2048)
    mdl = MipModel("tpu-matmul-blocks")
    vm = mdl.add_one_hot("bm", len(cm))
    vk = mdl.add_one_hot("bk", len(ck))
    vn = mdl.add_one_hot("bn", len(cn))
    dm = mdl.add_binary("psiDM")

    # HBM traffic (bytes): x re-read N/bn times, w re-read M/bm times,
    # out written once — the weight-reload analogue.
    traffic = LinExpr({}, float(m * n * bytes_acc))
    for c, v in zip(cn, vn):
        traffic = traffic + (m * k * bytes_in) * math.ceil(n / c) * v
    for c, v in zip(cm, vm):
        traffic = traffic + (k * n * bytes_in) * math.ceil(m / c) * v
    t_hbm_scale = 1.0 / HBM_BW
    t_mxu = 2.0 * m * n * k / MXU_FLOPS

    # working set: bm*bk + bk*bn + bm*bn*acc (+ scales, negligible)
    # pairwise products of one-hots -> enumerate (tiny sets)
    ws = mdl.add_var("ws", 0.0, float(vmem_bytes) * 4)
    for i, cmi in enumerate(cm):
        for j, ckj in enumerate(ck):
            for l2, cnl in enumerate(cn):
                w = cmi * ckj * bytes_in + ckj * cnl * bytes_in + \
                    cmi * cnl * bytes_acc
                big = float(vmem_bytes * 8)
                mdl.add_ge(ws - w + big * (3 - vm[i] - vk[j] - vn[l2]),
                           0.0)
    # capacity: ws + psi^DM * ws <= vmem  ->  ws + dbx <= vmem
    dbx = mdl.add_var("dbx", 0.0, float(vmem_bytes) * 4)
    mdl.add_ge(dbx - ws + float(vmem_bytes * 8) * (1 - dm * 1.0), 0.0)
    mdl.add_le(ws + dbx, float(vmem_bytes))

    t = mdl.add_var("T", 0.0, 1e6)
    # double-buffered: T >= max(t_hbm, t_mxu); single: T >= t_hbm + t_mxu
    mdl.add_ge(t - t_hbm_scale * traffic, 0.0)
    mdl.add_ge(t, t_mxu)
    big_t = 1e3
    mdl.add_ge(t - t_hbm_scale * traffic - t_mxu - big_t * (dm * 1.0),
               -0.0)
    mdl.minimize(t)
    sol = mdl.solve(time_limit_s=time_limit_s, mip_rel_gap=1e-4)
    if not sol.ok:
        return BlockChoice(256, 512, 256, True, math.nan, -1, "fallback")
    pick = lambda cs, vs: cs[max(range(len(cs)), key=lambda i: sol[vs[i]])]
    bm_v, bk_v, bn_v = pick(cm, vm), pick(ck, vk), pick(cn, vn)
    ws_v = bm_v * bk_v * bytes_in + bk_v * bn_v * bytes_in + \
        bm_v * bn_v * bytes_acc
    return BlockChoice(bm_v, bk_v, bn_v, sol.binary(dm), sol[t], ws_v,
                       sol.status.name)


def _snap(hint: int, dim: int, *, align: int) -> int:
    """Round a mapping tile extent up to MXU alignment and clamp it into
    [align, min(2048, dim padded to alignment)]."""
    padded = max(align, min(_round_up(dim, align), 2048))
    return max(align, min(_round_up(hint, align), padded))


def select_blocks_from_mapping(mapping, layer, arch, *,
                               bytes_in: int = 1, bytes_acc: int = 4,
                               vmem_bytes: int = VMEM_BYTES) -> BlockChoice:
    """Translate a solved MIREDO mapping into Pallas matmul block shapes.

    The measured-execution backend (`core/executor.py`) runs each optimized
    GEMM on kernels/matmul_int8; the block shapes come from the mapping the
    MIP actually chose rather than from a fresh bridge MIP: a dim's on-chip
    tile extent — spatial unrolls plus every temporal factor that *all*
    operands indexing the dim hold above DRAM — is the working set MIREDO
    decided to keep resident, i.e. the CIM analogue of the VMEM-resident
    Pallas block. The M and N extents are snapped to MXU alignment (lane
    128 / sublane 8) and clamped to the padded dim. Callers zero-pad when a
    block does not divide the dim (kernels/matmul_int8/ops.py), exactly as
    for `select_matmul_blocks` picks.

    The reduction block departs from the mapping: it is the whole K,
    padded to the lane, whenever the double-buffered eq. 9 working set
    still fits. The mapping's C tile is the macro's wordline count
    (`CimArch.macro_rows`), a limit of the CIM array, not of the TPU,
    whose kernel keeps the partial sums in a VMEM int32 accumulator: a
    further K step saves no HBM byte (x and w are read the same either
    way) and costs a grid step and a pass over that accumulator. Where the
    whole K does not fit, the C tile is snapped like the others and the
    working set is halved-down until eq. 9 holds. The int32 sum is exact,
    so the result does not depend on the blocks.
    """
    from repro.core import workload as wl

    m, k, n = layer.bound("N"), layer.bound("C"), layer.bound("K")
    hints = {d: 1 for d in ("N", "C", "K")}
    for ax in arch.spatial:
        for d, f in mapping.spatial.get(ax.name, ()):
            if d in hints:
                hints[d] *= f
    for i, (d, f) in enumerate(mapping.temporal):
        if d in hints and all(
                mapping.level_of[lam][i] >= 1
                for lam in mapping.level_of if wl.is_relevant(d, lam)):
            hints[d] *= f
    bm = _snap(hints["N"], m, align=SUBLANE)
    bn = _snap(hints["K"], n, align=LANE)
    bk = _round_up(k, LANE)
    ws = lambda: bm * bk * bytes_in + bk * bn * bytes_in + bm * bn * bytes_acc
    if 2 * ws() > vmem_bytes:         # whole K does not fit: map C too
        bk = _snap(hints["C"], k, align=LANE)
    while 2 * ws() > vmem_bytes:      # pipelined (double-buffered) eq. 9
        if bm >= max(bk, bn) and bm > SUBLANE:
            bm = max(SUBLANE, bm // 2 - bm // 2 % SUBLANE)
        elif bk >= bn and bk > LANE:
            bk = max(LANE, bk // 2 - bk // 2 % LANE)
        elif bn > LANE:
            bn = max(LANE, bn // 2 - bn // 2 % LANE)
        else:
            break
    return BlockChoice(bm, bk, bn, True, math.nan, ws(), "MAPPED")


def select_flash_blocks(seq_q: int, seq_k: int, head_dim: int, *,
                        bytes_el: int = 2,
                        vmem_bytes: int = VMEM_BYTES) -> tuple[int, int]:
    """Largest (block_q, block_k) whose pipelined working set fits VMEM —
    the degenerate (single-level) instance of eq. 9; closed-form, no solver
    needed, but uses the same accounting as select_matmul_blocks."""
    best = (128, 128)
    best_steps = math.inf
    for bq in (1024, 512, 256, 128):
        if seq_q % bq:
            continue
        for bk in (1024, 512, 256, 128):
            if seq_k % bk:
                continue
            ws = (bq * head_dim + 2 * bk * head_dim) * bytes_el + \
                bq * head_dim * 4 + bq * bk * 4
            if 2 * ws > vmem_bytes:     # double-buffered pipeline
                continue
            steps = (seq_q // bq) * (seq_k // bk)
            if steps < best_steps:
                best_steps, best = steps, (bq, bk)
    return best
