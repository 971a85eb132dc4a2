"""Property/differential tests for the request-level serving simulator
(`core/serving.py`) and its integration with the frontend and DSE layers.

The engine's guarantees (module docstring of ``serving.py``) are enforced
here, not just by benchmark gates:

* token conservation — every admitted request's tokens are emitted
  exactly once (seq numbers 1..output_len, in order), nobody starves;
* KV occupancy never exceeds ``kv_capacity_tokens``;
* the same seed produces a bit-identical event log;
* differential vs the serial baseline — with "reserve" admission the
  continuous-batching makespan is never worse, and strictly better when
  requests genuinely overlap (the affine cost model makes the strict
  bound exactly analyzable: each saved iteration saves ``base``).

Runs under ``hypothesis`` when available; otherwise a seeded-random
strategy shim (the tier-1 fallback pattern from
``tests/test_mapping_fuzz.py``) so the suite collects on a bare
environment.
"""

import collections
import random

import pytest

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

        @staticmethod
        def booleans():
            return _Strategy(lambda rng: rng.random() < 0.5)

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

from repro.core.serving import (AffineCostModel, Request, RequestStream,
                                ServeConfig, _SubadditiveClosure,
                                percentile, serial_baseline,
                                simulate_serving)


# --------------------------------------------------------------------------
# Shared invariant checker
# --------------------------------------------------------------------------

def _stream(seed: int, n: int = 12, rate: float = 800.0) -> RequestStream:
    return RequestStream.poisson(n, seed=seed,
                                 mean_interarrival_cycles=rate,
                                 prompt_lens=(2, 5, 9),
                                 output_lens=(1, 3, 6))


def assert_invariants(stream: RequestStream, rep, cfg: ServeConfig) -> None:
    """The properties every simulation must satisfy, derived from the
    event log — independently of the engine's own counters."""
    by_rid = {r.rid: r for r in stream.requests}
    fin = {m.rid for m in rep.finished}
    rej = set(rep.rejected)

    # No starvation: finished/rejected partition the stream exactly.
    assert fin.isdisjoint(rej)
    assert fin | rej == set(by_rid), "some request neither finished nor " \
        "was rejected (starvation or loss)"

    # Token conservation: each finished request emitted exactly
    # output_len tokens, sequence numbers 1..output_len in order; rejected
    # requests emitted nothing.
    toks = collections.defaultdict(list)
    for _t, kind, rid, aux in rep.events:
        if kind == "token":
            toks[rid].append(aux)
    for rid in fin:
        want = list(range(1, by_rid[rid].output_len + 1))
        assert toks[rid] == want, f"rid {rid}: tokens {toks[rid]} != {want}"
    for rid in rej:
        assert rid not in toks
    assert rep.total_output_tokens == sum(by_rid[r].output_len for r in fin)

    # KV capacity: the per-iteration occupancy recorded in the event log
    # (aux of "iter" events) never exceeds capacity.
    occs = [aux for _t, kind, _rid, aux in rep.events if kind == "iter"]
    assert all(o <= cfg.kv_capacity_tokens for o in occs)
    assert rep.max_kv_occupancy <= cfg.kv_capacity_tokens
    if occs:
        assert rep.max_kv_occupancy == max(occs)

    # Per-request causality: a request's own events are time-ordered.
    times = collections.defaultdict(list)
    for t, kind, rid, _aux in rep.events:
        if kind != "iter":
            times[rid].append(t)
    for rid, ts in times.items():
        assert all(b >= a for a, b in zip(ts, ts[1:]))

    # Metrics coherence.
    for m in rep.finished:
        assert m.ttft_cycles >= 0
        assert len(m.itls) == m.output_len - 1
        assert m.finish_cycles >= m.first_token_cycles


# --------------------------------------------------------------------------
# Property/fuzz: invariants + determinism under both admission policies
# --------------------------------------------------------------------------

@settings(max_examples=40)
@given(st.integers(0, 10_000),
       st.sampled_from((15, 16, 24, 48, 512)),      # kv capacity
       st.sampled_from((1, 2, 4, 64)),              # max batch requests
       st.sampled_from((2, 9, 16, 128)),            # max batch tokens
       st.booleans())                               # optimistic?
def test_fuzz_invariants_and_determinism(seed, kv_cap, mbr, mbt, opt):
    cfg = ServeConfig(kv_capacity_tokens=kv_cap, max_batch_requests=mbr,
                      max_batch_tokens=mbt,
                      admission="optimistic" if opt else "reserve")
    stream = _stream(seed)
    rep = simulate_serving(stream, AffineCostModel(), cfg)
    assert_invariants(stream, rep, cfg)
    # Bit-identical determinism: a fresh same-seed stream through a fresh
    # engine reproduces the event log exactly (tuple equality, no
    # tolerance).
    rerun = simulate_serving(_stream(seed), AffineCostModel(), cfg)
    assert rerun.events == rep.events
    assert rerun.makespan_cycles == rep.makespan_cycles


@settings(max_examples=40)
@given(st.integers(0, 10_000), st.sampled_from((0.0, 1.0, 100.0)),
       st.sampled_from((1.0, 10.0)))
def test_fuzz_differential_batched_never_worse(seed, base, per_token):
    """Differential vs the serial baseline on randomized streams: with
    "reserve" admission and a subadditive cost, continuous batching never
    loses — for any base/per_token, any seed."""
    cfg = ServeConfig(kv_capacity_tokens=4096, max_batch_requests=64,
                      max_batch_tokens=1024)
    cost = AffineCostModel(base=base, per_token=per_token)
    stream = _stream(seed)
    rep = simulate_serving(stream, cost, cfg)
    ser = serial_baseline(stream, cost, cfg)
    assert_invariants(stream, rep, cfg)
    assert rep.makespan_cycles <= ser.makespan_cycles + 1e-9
    # Serial really is serial.
    assert ser.max_concurrency <= 1
    assert ser.n_merged_iterations == 0


@settings(max_examples=40)
@given(st.integers(0, 10_000), st.integers(2, 8))
def test_fuzz_differential_strict_when_overlapping(seed, n):
    """With >= 2 requests overlapping (all arrive at t=0), ample capacity
    and a strictly subadditive cost (base > 0), batching is *strictly*
    better: the batched run uses fewer iterations than serial's
    sum(output_len), and each iteration saved saves >= base cycles."""
    rng = random.Random(seed)
    rows = [(0.0, rng.randint(1, 9), rng.randint(1, 6)) for _ in range(n)]
    stream = RequestStream.from_trace(rows)
    cfg = ServeConfig(kv_capacity_tokens=4096, max_batch_requests=64,
                      max_batch_tokens=1024)
    cost = AffineCostModel(base=100.0, per_token=10.0)
    rep = simulate_serving(stream, cost, cfg)
    ser = serial_baseline(stream, cost, cfg)
    assert_invariants(stream, rep, cfg)
    assert rep.n_merged_iterations >= 1
    # Everything admitted in iteration 1, so batched iterations =
    # max(output_len) < sum(output_len) = serial iterations; both runs
    # charge per_token identically per emitted/prefilled token, so the
    # gap is exactly base * (iterations saved).
    assert rep.n_iterations == max(o for _a, _p, o in rows)
    assert ser.n_iterations == sum(o for _a, _p, o in rows)
    saved = ser.n_iterations - rep.n_iterations
    assert saved >= 1
    assert rep.makespan_cycles == pytest.approx(
        ser.makespan_cycles - cost.base * saved)


# --------------------------------------------------------------------------
# Admission, preemption and rejection paths
# --------------------------------------------------------------------------

def test_reserve_capacity_gates_admission():
    """Capacity that fits exactly one worst-case request => the engine
    degenerates to serial, with zero preemptions, by admission alone."""
    rows = [(0.0, 8, 6)] * 5
    stream = RequestStream.from_trace(rows)
    cfg = ServeConfig(kv_capacity_tokens=14, max_batch_requests=64,
                      max_batch_tokens=64)
    rep = simulate_serving(stream, AffineCostModel(), cfg)
    assert_invariants(stream, rep, cfg)
    assert rep.max_concurrency == 1
    assert rep.n_preemptions == 0
    assert len(rep.finished) == 5


def test_optimistic_preemption_requeue_and_finish():
    """Tight capacity under "optimistic" admission: over-admission forces
    preemptions, yet every request still finishes with its exact token
    count and occupancy never exceeds capacity."""
    rows = [(0.0, 8, 6)] * 20
    stream = RequestStream.from_trace(rows)
    cfg = ServeConfig(kv_capacity_tokens=48, max_batch_requests=64,
                      max_batch_tokens=256, admission="optimistic")
    rep = simulate_serving(stream, AffineCostModel(), cfg)
    assert_invariants(stream, rep, cfg)
    assert rep.n_preemptions >= 1
    assert len(rep.finished) == 20
    assert rep.max_kv_occupancy <= 48
    # Preemption is visible in the log and in per-request metrics.
    assert any(kind == "preempt" for _t, kind, _r, _a in rep.events)
    assert sum(m.n_preemptions for m in rep.finished) == rep.n_preemptions


def test_infeasible_requests_rejected_up_front():
    cfg = ServeConfig(kv_capacity_tokens=16, max_batch_requests=4,
                      max_batch_tokens=8)
    rows = [(0.0, 4, 2),      # fits
            (1.0, 12, 8),     # prompt+output=20 > kv 16  -> reject
            (2.0, 9, 2),      # prefill 9 > max_batch_tokens 8 -> reject
            (3.0, 8, 8)]      # fits exactly
    stream = RequestStream.from_trace(rows)
    rep = simulate_serving(stream, AffineCostModel(), cfg)
    assert_invariants(stream, rep, cfg)
    assert set(rep.rejected) == {1, 2}
    assert {m.rid for m in rep.finished} == {0, 3}
    # Under "optimistic" the worst re-prefill covers prompt+generated, so
    # the last request (8+8-1=15 tokens > 8) becomes infeasible too.
    opt = ServeConfig(kv_capacity_tokens=16, max_batch_requests=4,
                      max_batch_tokens=8, admission="optimistic")
    rep_o = simulate_serving(stream, AffineCostModel(), opt)
    assert set(rep_o.rejected) == {1, 2, 3}


def test_validation_errors():
    with pytest.raises(ValueError):
        Request(0, 0.0, 0, 1)
    with pytest.raises(ValueError):
        Request(0, 0.0, 1, 0)
    with pytest.raises(ValueError):
        RequestStream((Request(0, 5.0, 1, 1), Request(1, 2.0, 1, 1)))
    with pytest.raises(ValueError):
        ServeConfig(admission="greedy")
    with pytest.raises(ValueError):
        ServeConfig(kv_capacity_tokens=0)
    with pytest.raises(ValueError):
        AffineCostModel(base=-1.0)
    with pytest.raises(ValueError):
        _SubadditiveClosure(lambda m: float(m), 0)


# --------------------------------------------------------------------------
# Cost models: subadditive closure, affine, percentile
# --------------------------------------------------------------------------

@settings(max_examples=25)
@given(st.integers(0, 10_000))
def test_subadditive_closure_is_monotone_and_subadditive(seed):
    """Random (deliberately non-monotone, super-additive) raw anchor costs:
    the closure must still come out monotone and subadditive, and never
    exceed the raw anchor value."""
    rng = random.Random(seed)
    raw = {}

    def raw_fn(m):
        raw[m] = rng.uniform(1.0, 1000.0)
        return raw[m]

    cl = _SubadditiveClosure(raw_fn, 64)
    assert set(raw) == {1, 2, 4, 8, 16, 32, 64}
    f = [cl.cycles(m) for m in range(65)]
    assert f[0] == 0.0
    for m in range(1, 65):
        assert f[m] >= f[m - 1] - 1e-12                    # monotone
        for j in range(1, m):
            assert f[m] <= f[j] + f[m - j] + 1e-9          # subadditive
    for a, r in raw.items():
        assert f[a] <= r + 1e-12                           # never above raw
    with pytest.raises(ValueError):
        cl.cycles(65)


def test_affine_cost_model():
    c = AffineCostModel(base=100.0, per_token=10.0, freq_ghz=2.0)
    assert c.cycles(0) == 0.0
    assert c.cycles(1) == 110.0
    assert c.cycles(7) == 170.0
    assert c.seconds(1) == pytest.approx(110.0 / 2e9)


def test_percentile_nearest_rank():
    vals = list(range(1, 101))
    random.Random(0).shuffle(vals)
    assert percentile(vals, 50) == 50
    assert percentile(vals, 99) == 99
    assert percentile(vals, 100) == 100
    assert percentile(vals, 0) == 1
    assert percentile([7.0], 99) == 7.0
    assert percentile([], 50) == 0.0


def test_poisson_stream_deterministic_and_trace_parsing(tmp_path):
    a = RequestStream.poisson(16, seed=3, mean_interarrival_cycles=100.0)
    b = RequestStream.poisson(16, seed=3, mean_interarrival_cycles=100.0)
    c = RequestStream.poisson(16, seed=4, mean_interarrival_cycles=100.0)
    assert a.requests == b.requests
    assert a.requests != c.requests
    arr = [r.arrival_cycles for r in a.requests]
    assert arr == sorted(arr)

    p = tmp_path / "trace.txt"
    p.write_text("# arrival prompt output\n10.0, 4, 2\n5.0 8 1\n\n")
    s = RequestStream.from_trace(str(p))
    assert [(r.arrival_cycles, r.prompt_len, r.output_len)
            for r in s.requests] == [(5.0, 8, 1), (10.0, 4, 2)]


# --------------------------------------------------------------------------
# Frontend integration: mixed batch composition -> exact m_tokens
# --------------------------------------------------------------------------

def test_serving_iteration_lowers_to_exact_m_tokens():
    """Pinned regression: a mixed prefill/decode batch of known
    composition — two prefills (5 and 7 prompt tokens) + three decode
    streams — lowers to exactly m_tokens = 15 on every weight GEMM,
    through `ShapeSpec.serving_iteration` -> `frontend.extract_workload`."""
    from repro.configs import get_config
    from repro.configs.base import ShapeSpec
    from repro.core.frontend import extract_workload

    spec = ShapeSpec.serving_iteration((5, 7), 3, context_len=64)
    assert spec.m_tokens == 15
    assert spec.kind == "decode"

    cfg = get_config("minicpm-2b").reduced()
    work = extract_workload(cfg, spec)
    got = {l.name.split(".")[-1]: (l.bound("N"), l.bound("K"),
                                   l.bound("C"), c)
           for l, c in zip(work.layers, work.counts)}
    assert got == {
        "wq": (15, 64, 64, 2), "wo": (15, 64, 64, 2),
        "wk": (15, 64, 64, 2), "wv": (15, 64, 64, 2),
        "ffn_up": (15, 256, 64, 2), "ffn_down": (15, 64, 128, 2),
        "lm_head": (15, 2048, 64, 1),
    }

    # SSM family: projections carry M = m_tokens, and the per-token SSD
    # ops' instance counts scale linearly in m (one scan step per token).
    mcfg = get_config("mamba2-1.3b").reduced()
    for m in (15, 4):
        mspec = ShapeSpec.serving_iteration((), m, context_len=64)
        mwork = extract_workload(mcfg, mspec)
        counts = {l.name.split(".")[-1]: c
                  for l, c in zip(mwork.layers, mwork.counts)}
        assert all(l.bound("N") in (m, 1, 16)
                   for l in mwork.layers)
        proj = {l.name.split(".")[-1]: l.bound("N") for l in mwork.layers}
        assert proj["in_proj"] == m and proj["out_proj"] == m
        assert counts["ssd_state_upd"] % m == 0
        assert counts["ssd_state_upd"] // m == \
            counts["ssd_readout"] // m  # same per-token replication
    # and the per-token ratio is identical across m values
    r15 = extract_workload(mcfg, ShapeSpec.serving_iteration((), 15))
    r4 = extract_workload(mcfg, ShapeSpec.serving_iteration((), 4))
    c15 = dict(zip((l.name for l in r15.layers), r15.counts))
    c4 = dict(zip((l.name for l in r4.layers), r4.counts))
    ssd = "mamba2-1.3b.blk.ssd_state_upd"
    assert c15[ssd] * 4 == c4[ssd] * 15

    with pytest.raises(ValueError):
        ShapeSpec.serving_iteration((), 0)


def test_extract_all_accepts_mixed_names_and_specs():
    from repro.configs import get_config
    from repro.configs.base import ShapeSpec
    from repro.core.frontend import extract_all

    cfg = get_config("minicpm-2b").reduced()
    spec = ShapeSpec.serving_iteration((3,), 2, context_len=64)
    out = extract_all(cfg, ["decode_32k", spec])
    assert "decode_32k" in out
    assert spec.name in out
    assert out[spec.name].layers[0].bound("N") == 5
    with pytest.raises(KeyError):
        extract_all(cfg, ["decode_32k", "no_such_scenario"])


# --------------------------------------------------------------------------
# Real-stack integration: NetworkCostModel differential
# --------------------------------------------------------------------------

def test_network_cost_model_differential_real_stack():
    """Iteration costs from the real stack (reduced minicpm, greedy
    mapper): the closure is monotone+subadditive, batching a second token
    is strictly cheaper than two single-token passes, and the serving
    differential holds end to end."""
    from repro.configs import get_config
    from repro.core.arch import default_arch
    from repro.core.serving import NetworkCostModel

    cfg = get_config("minicpm-2b").reduced()
    cost = NetworkCostModel(cfg, default_arch(), max_m=32,
                            context_len=256, mode="greedy",
                            per_layer_cap_s=1.0)
    assert cost.n_solves == 6           # anchors 1,2,4,8,16,32
    assert set(cost.anchor_cycles) == {1, 2, 4, 8, 16, 32}
    f = [cost.cycles(m) for m in range(33)]
    for m in range(1, 33):
        assert f[m] >= f[m - 1] - 1e-9
        for j in range(1, m):
            assert f[m] <= f[j] + f[m - j] + 1e-6
    # The whole point of batching: merging is strictly cheaper than
    # running back to back (weights are re-streamed once, not twice).
    assert cost.cycles(2) < 2 * cost.cycles(1)

    rows = [(0.0, 8, 4), (0.0, 4, 6), (1000.0, 16, 4)]
    stream = RequestStream.from_trace(rows)
    scfg = ServeConfig(kv_capacity_tokens=256, max_batch_requests=8,
                       max_batch_tokens=32)
    rep = simulate_serving(stream, cost, scfg)
    ser = serial_baseline(stream, cost, scfg)
    assert_invariants(stream, rep, scfg)
    assert rep.n_merged_iterations >= 1
    assert rep.makespan_cycles < ser.makespan_cycles


# --------------------------------------------------------------------------
# DSE integration: goodput-vs-latency ranking divergence
# --------------------------------------------------------------------------

def test_goodput_vs_latency_ranking_differs():
    """The mechanism behind `rank_by="slo_goodput"` (and the serve_sim
    benchmark gate that references this test): two archs whose iteration
    cost curves *cross*.  Arch A has low fixed cost but poor batching
    (high per-token cost); arch B pays more per pass but amortizes across
    a merged batch.  Single-token latency ranks A first; sustained
    tokens/sec under traffic ranks B first — so the latency-ranked and
    goodput-ranked Pareto frontiers genuinely differ."""
    from repro.core.dse import DsePoint, pareto_frontier

    cost_a = AffineCostModel(base=10.0, per_token=5.0)    # latency winner
    cost_b = AffineCostModel(base=50.0, per_token=1.0)    # batching winner
    assert cost_a.cycles(1) < cost_b.cycles(1)
    assert cost_a.cycles(32) > cost_b.cycles(32)          # curves cross

    stream = RequestStream.from_trace([(0.0, 8, 8)] * 8)  # bursty overlap
    cfg = ServeConfig(kv_capacity_tokens=4096, max_batch_requests=64,
                      max_batch_tokens=1024)
    goodput = {}
    for name, cost in (("A", cost_a), ("B", cost_b)):
        rep = simulate_serving(stream, cost, cfg)
        goodput[name] = rep.goodput_tokens_per_sec(cost.freq_ghz)
    assert goodput["B"] > goodput["A"]                    # ranking flips

    def points(rank_by):
        return [DsePoint(arch_name=n, cycles=c.cycles(1), energy_pj=1.0,
                         area_bits=1024, serial_cycles=c.cycles(1),
                         goodput_tok_s=goodput[n], rank_by=rank_by)
                for n, c in (("A", cost_a), ("B", cost_b))]

    lat = [p.arch_name for p in pareto_frontier(points("latency"))]
    good = [p.arch_name for p in pareto_frontier(points("slo_goodput"))]
    assert lat == ["A"]     # B dominated: worse cycles, same energy/area
    assert good == ["B"]    # A dominated: worse goodput, same energy/area
    assert lat != good


def test_rank_by_validation():
    from repro.core.dse import ArchSpace, DsePoint, run_dse

    p = DsePoint(arch_name="x", cycles=1.0, energy_pj=1.0, area_bits=1,
                 serial_cycles=1.0, rank_by="slo_goodput")
    with pytest.raises(ValueError):
        p.objectives()          # goodput missing
    space = ArchSpace(macro=((64, 32),), n_cores=(4,), gbuf_kb=(8.0,),
                      lbuf_kb=(16.0,))
    with pytest.raises(ValueError):
        run_dse([], None, space, "greedy", rank_by="slo_goodput")
    with pytest.raises(ValueError):
        run_dse([], None, space, "greedy", rank_by="edp")


def test_arch_goodput_scenario():
    from repro.core.arch import default_arch
    from repro.core.serving import ServeScenario, arch_goodput

    scen = ServeScenario(model_ids=("minicpm-2b",), reduced=True,
                         n_requests=6, context_len=256,
                         serve=ServeConfig(kv_capacity_tokens=256,
                                           max_batch_requests=8,
                                           max_batch_tokens=32),
                         per_layer_cap_s=1.0)
    out = arch_goodput(scen, default_arch())
    assert set(out) == {"minicpm-2b", "mean"}
    assert out["mean"] == pytest.approx(out["minicpm-2b"])
    assert out["mean"] > 0


# --------------------------------------------------------------------------
# KV-cache max_seq regression (examples/serve_lm.py satellite)
# --------------------------------------------------------------------------

def test_decode_cache_sized_to_prompt_plus_gen():
    """Regression for the hardcoded ``max_seq = 64`` bug in
    examples/serve_lm.py: the decode step writes at each row's length
    and *silently drops* writes past the padded cache length.
    Sizing the cache to exactly prompt + generated must keep every write
    in bounds: the final cache length equals prompt+gen and the last
    position really was written (nonzero keys)."""
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.train.steps import (StepConfig, decode_caches,
                                   init_train_state, make_decode_step,
                                   make_prefill_step)

    cfg = get_config("minicpm-2b").reduced()
    step_cfg = StepConfig(remat=False, compute_dtype=jnp.float32)
    state = init_train_state(jax.random.PRNGKey(0), cfg, step_cfg)
    batch, prompt_len, gen_len = 2, 4, 3
    max_seq = prompt_len + gen_len

    rng = np.random.default_rng(0)
    prompt = jnp.asarray(rng.integers(0, cfg.vocab_size,
                                      (batch, prompt_len)), jnp.int32)
    prefill = jax.jit(make_prefill_step(cfg, step_cfg))
    decode = jax.jit(make_decode_step(cfg, step_cfg))
    logits, caches = prefill(state.params, {"tokens": prompt})
    caches = decode_caches(cfg, caches, batch, max_seq,
                           step_cfg.compute_dtype)

    tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
    for _ in range(gen_len):
        logits, caches = decode(state.params, {"tokens": tok}, caches)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]

    lengths = np.asarray(caches.length)
    assert int(lengths.max()) == prompt_len + gen_len <= max_seq
    assert np.all(lengths == lengths.max())
    # The last decode's KV landed at the final slot — a dropped scatter
    # (undersized cache) would leave it all-zero.
    assert np.any(np.asarray(caches.k)[:, :, max_seq - 1] != 0)
