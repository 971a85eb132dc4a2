"""Differential sweep: the batched analytical model vs the scalar oracle.

`latency_batched` promises *bit-equality* with `latency.evaluate` /
`energy.evaluate_edp` (DESIGN.md §Batched analytical model): every float op
replayed in the scalar order under float64, padding provably inert. These
tests enforce the promise with exact ``==`` — no tolerances — across random
(layer, arch, pool) draws, including the edge cases the
packing has to get right:

  * mixed slot counts in one pool (right-aligned identity padding),
  * operands with no transfers at all (DRAM-resident level chains),
  * weight hops into the macro level (mode-switch cycles),
  * capacity-infeasible rows (gated packs must return ``inf``; ungated
    packs must still reproduce the scalar numbers for those rows).

Runs under ``hypothesis`` when available, else the seeded-random shim from
``tests/test_mapping_fuzz.py``. Also holds the `baselines._assign_levels`
shared-level budget regression (the fair-share fix this PR lands).
"""

import math
import random

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                                   # seeded fallback
    class _Strategy:
        def __init__(self, draw):
            self._draw = draw

        def draw(self, rng):
            return self._draw(rng)

    class _Strategies:
        @staticmethod
        def integers(lo, hi):
            return _Strategy(lambda rng: rng.randint(lo, hi))

        @staticmethod
        def sampled_from(seq):
            return _Strategy(lambda rng: rng.choice(list(seq)))

    st = _Strategies()

    def given(*strategies):
        def deco(fn):
            def wrapper(*args, **kwargs):
                rng = random.Random(0)
                n = getattr(fn, "_max_examples", 25)
                for _ in range(n):
                    fn(*args, *(s.draw(rng) for s in strategies), **kwargs)
            wrapper.__name__ = fn.__name__
            wrapper.__doc__ = fn.__doc__
            return wrapper
        return deco

    def settings(max_examples=25, **_kwargs):
        def deco(fn):
            fn._max_examples = max_examples
            return fn
        return deco

from repro.core import latency_batched as lb
from repro.core import workload as wl
from repro.core.arch import default_arch
from repro.core.baselines import greedy_mapping, sample_mapping_raw
from repro.core.energy import evaluate_edp
from repro.core.factorization import factorize_layer_dims
from repro.core.latency import idealized_cycles
from repro.core.mapping import validate

#: Same arch grid as the mapping fuzz: spans core count, macro geometry,
#: buffer capacities and the double-buffering policy.
ARCHS = (
    default_arch(),
    default_arch(n_cores=2, macro_rows=64, macro_cols=16, gbuf_kb=2.0,
                 lbuf_kb=8.0, name="lb-tiny"),
    default_arch(double_buffered=False, name="lb-single-buf"),
    default_arch(n_cores=4, macro_rows=256, macro_cols=64, lbuf_kb=16.0,
                 reg_bytes=512, name="lb-wide"),
)
DIM_CHOICES = (3, 8, 24, 100, 128, 360)


def _layer(kind: int, a: int, b: int, c: int) -> wl.Layer:
    if kind == 0:
        return wl.gemm("lb.gemm", a, b, c)
    return wl.conv("lb.conv", 1, a, c, min(b, 28), min(b, 28), 3, 3)


def _pool(layer, arch, n, seed):
    """greedy (few slots) + raw samples (varying slots, ~90% capacity-
    infeasible): one pool exercises mixed slot counts, padded rows, macro
    weight hops and the gated-inf path all at once."""
    rng = random.Random(seed)
    factors = factorize_layer_dims({d: layer.bound(d) for d in wl.DIMS})
    return [greedy_mapping(layer, arch)] + [
        sample_mapping_raw(layer, arch, rng, factors) for _ in range(n)]


def _assert_rows_exact(sc, pool, layer, arch, feas):
    for i, mp in enumerate(pool):
        where = f"{arch.name}/{layer.name} row {i}"
        if feas[i]:
            e = evaluate_edp(mp, layer, arch)
            assert float(sc.cycles[i]) == e.latency.total_cycles, where
            assert float(sc.energy_pj[i]) == e.energy.total_pj, where
            assert float(sc.edp[i]) == e.edp, where
            assert float(sc.idealized[i]) == \
                idealized_cycles(mp, layer, arch), where
        else:
            assert math.isinf(float(sc.cycles[i])), where
            assert math.isinf(float(sc.edp[i])), where


@given(st.integers(0, 1),
       st.sampled_from(DIM_CHOICES), st.sampled_from(DIM_CHOICES),
       st.sampled_from(DIM_CHOICES), st.integers(0, len(ARCHS) - 1),
       st.integers(0, 10_000))
@settings(max_examples=8, deadline=None)
def test_differential_sweep_exact(kind, a, b, c, ai, seed):
    """Every batched score equals the scalar oracle bit-for-bit; the
    feasibility vector equals ``validate``'s verdict (sampler-constructed
    candidates can only violate the eq. 9 clause the gate checks)."""
    layer, arch = _layer(kind, a, b, c), ARCHS[ai]
    pool = _pool(layer, arch, 24, seed)
    feas = [not validate(mp, layer, arch) for mp in pool]
    sc = lb.score_mappings(pool, layer, arch)
    assert list(map(bool, sc.feasible)) == feas
    _assert_rows_exact(sc, pool, layer, arch, feas)


def test_mixed_slot_counts_padding_inert():
    """A mapping's scores are identical whether it is scored alone or
    packed into a pool of mappings with different slot counts — the
    right-aligned identity padding and the slot/batch bucketing must be
    arithmetically invisible."""
    layer, arch = wl.gemm("lb.pad", 32, 512, 512), ARCHS[0]
    pool = _pool(layer, arch, 40, seed=3)
    assert len({mp.n_slots() for mp in pool}) > 1, "pool must mix widths"
    feas = [not validate(mp, layer, arch) for mp in pool]
    together = lb.score_mappings(pool, layer, arch)
    for i in (0, len(pool) // 2, len(pool) - 1):
        alone = lb.score_mappings([pool[i]], layer, arch)
        for field in ("cycles", "energy_pj", "edp", "idealized"):
            t = float(getattr(together, field)[i])
            s = float(getattr(alone, field)[0])
            assert t == s or (math.isinf(t) and math.isinf(s)), \
                (i, field, t, s)
    _assert_rows_exact(together, pool, layer, arch, feas)


def test_ungated_pack_scores_infeasible_rows():
    """Omitting 'feasible' from ``need`` disables the capacity gate: every
    row — including capacity-violating ones — must reproduce the scalar
    model's numbers (the analytical recursion is defined regardless of
    eq. 9; gating is a scoring policy, not a model property)."""
    layer, arch = wl.gemm("lb.ungated", 32, 512, 512), ARCHS[1]
    pool = _pool(layer, arch, 30, seed=5)
    infeasible = [mp for mp in pool if validate(mp, layer, arch)]
    assert infeasible, "pool must contain capacity-infeasible rows"
    pb = lb.pack(pool, layer, arch, need=("latency", "energy"))
    assert not pb.gated
    sc = lb.evaluate_batch(pb)
    for i, mp in enumerate(pool):
        e = evaluate_edp(mp, layer, arch)
        assert float(sc.cycles[i]) == e.latency.total_cycles
        assert float(sc.energy_pj[i]) == e.energy.total_pj


def test_empty_and_singleton_pools():
    layer, arch = wl.gemm("lb.edge", 8, 64, 64), ARCHS[0]
    sc = lb.score_mappings([], layer, arch)
    assert len(sc.cycles) == 0 and len(sc.feasible) == 0
    g = greedy_mapping(layer, arch)
    e = evaluate_edp(g, layer, arch)
    one = lb.score_mappings([g], layer, arch)
    assert bool(one.feasible[0])
    assert float(one.cycles[0]) == e.latency.total_cycles
    assert float(one.edp[0]) == e.edp


def test_assign_levels_shared_budget_regression():
    """`baselines._assign_levels` must budget shared levels at a fair
    share per served operand. The old expression (``cap if shared else
    cap``) granted full capacity to each operand in isolation, the
    combined placement over-committed the level, final validation failed
    and greedy fell back to streaming everything from DRAM. On this
    pinned config the fixed sweep keeps at least one non-weight operand
    on-chip; the all-DRAM fallback is the regression signature."""
    arch = default_arch(gbuf_kb=0.5, lbuf_kb=2.0, reg_bytes=128,
                        name="lb-shared-tight")
    layer = wl.gemm("lb.shared", 32, 512, 512)
    mp = greedy_mapping(layer, arch)
    assert validate(mp, layer, arch) == []
    on_chip = any(m != 0 for lam in ("I", "O")
                  for m in mp.level_of[lam])
    assert on_chip, ("greedy hit the all-DRAM fallback: the shared-level "
                     "capacity sweep over-committed (fair-share budget "
                     "regression)")
    # the fair-share placement must still respect the hard eq. 9 bound
    for m in range(arch.n_levels):
        cap = mp.eff_capacity(arch, m)
        if cap is None or not arch.level(m).shared:
            continue
        used = sum(
            (2 if mp.is_double_buffered(lam, m, arch) else 1) *
            mp.stored_bytes(layer, lam, arch, m)
            for lam in mp.level_of
            if m in mp.used_levels(lam) and arch.serves(m, lam))
        assert used <= cap + 1e-6, (m, used, cap)
