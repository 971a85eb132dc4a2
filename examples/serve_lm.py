"""Serving example: prefill + batched autoregressive decode with KV caches
(reduced glm4-9b config on CPU; the same step functions the chip
benchmark runs), then the CIM side of the same question: the model
frontend (core/frontend.py) lowers this exact serving config to its
weight-GEMM workload, MIREDO reports the optimized dataflow mapping, and
the measured-execution backend (core/executor.py) actually *runs* the
served decode step's optimized plan on the Pallas kernels — every kernel
checked against its ref.py oracle, wall-clock vs predicted cycles.

    PYTHONPATH=src python examples/serve_lm.py

``--traffic`` skips the single-step demo and instead serves a seeded
Poisson request stream through the request-level simulator
(core/serving.py): continuous batching vs the serial baseline, with
iteration costs anchored on this config's own scheduled solves.

    PYTHONPATH=src python examples/serve_lm.py --traffic
"""

import argparse
import time


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--traffic", action="store_true",
                    help="traffic-driven mode: serve a Poisson request "
                         "stream through the continuous-batching "
                         "simulator instead of the single-step demo")
    ap.add_argument("--n-requests", type=int, default=16,
                    help="stream length for --traffic")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    if args.traffic:
        traffic_demo(n_requests=args.n_requests, seed=args.seed)
    else:
        decode_demo()
    print("OK")


def decode_demo():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.train.steps import (StepConfig, decode_caches,
                                   init_train_state, make_decode_step,
                                   make_prefill_step)

    cfg = get_config("glm4-9b").reduced()
    step_cfg = StepConfig(remat=False, compute_dtype=jnp.float32)
    state = init_train_state(jax.random.PRNGKey(0), cfg, step_cfg)
    params = state.params
    batch, prompt_len, gen_len = 4, 12, 20
    # The KV cache needs exactly prompt + generated positions: the decode
    # step writes one position per call at the row's length, and
    # silently drops any write past the padded length — so an undersized
    # max_seq truncates the cache while the token loop keeps "working".
    max_seq = prompt_len + gen_len

    rng = np.random.default_rng(0)
    prompt = jnp.asarray(rng.integers(0, cfg.vocab_size,
                                      (batch, prompt_len)), jnp.int32)
    prefill = jax.jit(make_prefill_step(cfg, step_cfg))
    decode = jax.jit(make_decode_step(cfg, step_cfg))

    t0 = time.monotonic()
    logits, caches = prefill(params, {"tokens": prompt})
    caches = decode_caches(cfg, caches, batch, max_seq, step_cfg.compute_dtype)
    print(f"prefill {batch}x{prompt_len}: {time.monotonic()-t0:.2f}s")

    toks = [jnp.argmax(logits, -1).astype(jnp.int32)[:, None]]
    t0 = time.monotonic()
    for _ in range(gen_len):
        logits, caches = decode(params, {"tokens": toks[-1]}, caches)
        toks.append(jnp.argmax(logits, -1).astype(jnp.int32)[:, None])
    dt = time.monotonic() - t0
    out = jnp.concatenate(toks, axis=1)
    print(f"decoded {gen_len} tokens/seq x {batch} seqs in {dt:.2f}s "
          f"({batch*gen_len/dt:.1f} tok/s on CPU)")
    print("sample token ids:", np.asarray(out[0])[:12], "...")
    assert out.shape == (batch, gen_len + 1)
    assert np.all(np.asarray(out) >= 0)
    # The decode loop must never have written past max_seq: the final
    # cache length is exactly every prompt + generated token, and the last
    # written position is in bounds (a dropped scatter would leave it 0).
    final_len = int(np.max(np.asarray(caches.length)))
    assert final_len == prompt_len + gen_len <= max_seq, \
        f"cache length {final_len} != {prompt_len + gen_len}"
    assert np.any(np.asarray(caches.k)[:, :, max_seq - 1] != 0), \
        "last decode wrote past the padded cache (write was dropped)"

    report_cim_dataflow(cfg, batch, context_len=max_seq)


def traffic_demo(n_requests: int = 16, seed: int = 0):
    """Serve a request stream against the same reduced config: iteration
    costs from the real stack, continuous batching vs serial baseline."""
    from repro.configs import get_config
    from repro.core.arch import default_arch
    from repro.core.serving import (NetworkCostModel, RequestStream,
                                    ServeConfig, serial_baseline,
                                    simulate_serving)

    cfg = get_config("glm4-9b").reduced()
    arch = default_arch()
    serve_cfg = ServeConfig(kv_capacity_tokens=512, max_batch_requests=16,
                            max_batch_tokens=128)
    cost = NetworkCostModel(cfg, arch, max_m=serve_cfg.max_batch_tokens,
                            context_len=256, mode="greedy")
    stream = RequestStream.poisson(n_requests, seed=seed,
                                   mean_interarrival_cycles=150_000.0)
    rep = simulate_serving(stream, cost, serve_cfg)
    ser = serial_baseline(stream, cost, serve_cfg)
    f = cost.freq_ghz
    s, ss = rep.summary(f), ser.summary(f)
    to_ms = 1.0 / (f * 1e6)
    print(f"served {n_requests} requests on {arch.name} "
          f"({cost.n_solves} anchor solves):")
    print(f"  TTFT p50/p99: {s['ttft_p50_cycles'] * to_ms:.3f} / "
          f"{s['ttft_p99_cycles'] * to_ms:.3f} ms   "
          f"ITL p50/p99: {s['itl_p50_cycles'] * to_ms:.3f} / "
          f"{s['itl_p99_cycles'] * to_ms:.3f} ms")
    print(f"  continuous batching: {s['tokens_per_sec']:.4g} tok/s "
          f"({int(s['n_merged_iterations'])} merged iterations) vs "
          f"serial {ss['tokens_per_sec']:.4g} tok/s")
    assert rep.makespan_cycles <= ser.makespan_cycles


def report_cim_dataflow(cfg, batch: int, budget_s: float = 2.0,
                        context_len: int = 64):
    """What dataflow should a CIM accelerator use for this serving config?

    Lowers the decode step of the served config to its weight-GEMM
    workload and runs the network pipeline (one MIP per unique GEMM,
    warm-started so the capped solves stay feasible)."""
    from repro.configs.base import ShapeSpec
    from repro.core.arch import default_arch
    from repro.core.frontend import extract_workload
    from repro.core.network import optimize_network

    arch = default_arch()
    # seq_len is the serving context: the decode GEMMs only see the batch
    # (m_tokens), but the executor's decode attention step attends a KV
    # cache of this length — seq_len=1 would make it a one-key softmax.
    spec = ShapeSpec("serve_decode", seq_len=context_len,
                     global_batch=batch, kind="decode")
    work = extract_workload(cfg, spec)
    # workers=1: this process already initialized JAX; forking a solver
    # pool after that risks deadlock, and the reduced config only has a
    # handful of unique solves anyway.
    net = optimize_network(list(work.layers), arch, "miredo",
                           counts=list(work.counts),
                           per_layer_cap_s=budget_s, workers=1)
    print(f"\nCIM dataflow for {cfg.name} decode (batch={batch}): "
          f"{len(work)} GEMMs, {net.n_unique} unique solves, "
          f"aggregate EDP {net.totals['edp']:.3e} "
          f"({net.totals['cycles']:.3g} cycles serial-sum)")
    s = net.scheduled
    print(f"multi-core schedule: {s['cycles']:.3g} cycles end-to-end "
          f"({s['serial_cycles'] / max(s['cycles'], 1.0):.2f}x vs serial, "
          f"{int(s['n_segments'])} segments, {int(s['n_packed'])} packed "
          f"weight-resident)")
    top = max(net.layers, key=lambda lr: lr.edp * lr.count)
    mp = top.record["mapping"]
    # GEMM-speak (M x K) @ (K x N): loop-nest N=M, C=K(reduction), K=N
    print(f"heaviest GEMM {top.layer.name} "
          f"(M={top.layer.bound('N')}, N={top.layer.bound('K')}, "
          f"K={top.layer.bound('C')}) x{top.count}:")
    print("  spatial :", mp["spatial"])
    print("  temporal:", mp["temporal"])
    print("  dbl-buf :", mp["double_buf"])

    # And actually RUN the served decode step's optimized plan on the
    # Pallas kernels (interpret mode: this example runs on the CPU): every
    # GEMM on matmul_int8 with mapping-derived blocks, the decode attention
    # step on flash_attention against the KV cache, each invocation checked
    # against its ref.py.
    from repro.core.executor import execute_plan, lower_plan
    plan = lower_plan(cfg, spec, net, arch)
    rep = execute_plan(plan, interpret=True)
    rank = f"{rep.rank_corr:.2f}" if rep.rank_corr is not None else "n/a"
    print(f"measured execution: {rep.n_unique} unique kernels "
          f"({rep.n_ops} ops), {rep.measured_total_s * 1e3:.1f} ms "
          f"wall-clock vs {net.totals['cycles']:.3g} predicted cycles, "
          f"rank corr {rank}, numerics "
          f"{'OK' if rep.numerics_ok else 'FAILED'} "
          f"(max rel err {rep.max_rel_err:.1e})")
    assert rep.numerics_ok, "kernel output diverged from its ref oracle"


if __name__ == "__main__":
    main()
