#!/usr/bin/env python3
"""Bring-up check: the optimized plan and zamba2-1.2b serving on one TPU.

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --chips 4    # glm4-9b sharded over a 4-chip host

One process runs every phase and owns the chip; the MIP solves stay in it
(``workers=1``), so no child ever loads JAX.

  device   JAX's first device must be a TPU whose kind is in the peaks
           table (`repro/chips.py`).
  plan     zamba2-1.2b at its published widths, prefill (1 x 2048) and
           decode (16 sequences, 2048 context): extract the workload, solve
           every unique layer's MIP, lower the plan and run each unique op
           on the compiled kernels. Every op must match its ``ref.py``
           oracle, and all three kernel families must run.
  serve    zamba2-1.2b with random bf16 weights: prefill 4 x 1024 tokens,
           decode 32 more, then prefill again with the flash-attention
           kernel inside the model; its last-position logits must match
           the plain prefill's within ``FLASH_TOL``.

``--chips 4`` runs only the sharded phase: glm4-9b at full depth (QKV
bias, the half-width interleaved rotary, 2 KV heads whose cache is split
on its sequence axis over the 4 chips), its
parameters made shard by shard on a (data=1, model=4) mesh, prefills
1 x 2048 tokens and decodes 8; then a 2-layer cut at the same widths runs
sharded and on one device, and the two last-position logits must agree
within ``SHARD_TOL``.

Weights and tokens are made from ``--seed``; nothing is downloaded. A
failed check exits non-zero without the result line. The last line of
standard output is ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

#: Relative L2 distance allowed between the last-position logits of two
#: bf16 runs of one model. bf16 keeps 8 bits of mantissa (2^-8 = 3.9e-3
#: per rounding) and the differences compound through the layers; a wrong
#: kernel or a lost reduction gives O(1).
#: Flash vs plain prefill: the plain path rounds the softmax probabilities
#: to bf16, the kernel keeps them in f32. Each application of the shared
#: attention block added about 5e-3 in CPU runs at reduced widths, and
#: zamba2-1.2b applies it 6 times.
FLASH_TOL = 6e-2
#: Sharded vs one device: partial sums reduced across chips in bf16; CPU
#: runs of 2 layers at widths 1024 and 2048 on 4 devices gave 1.3e-2.
SHARD_TOL = 5e-2

KERNELS = {"matmul_int8", "flash_attention", "ssd_scan"}


class PhaseFailed(RuntimeError):
    pass


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise PhaseFailed(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


def _rel(a, b) -> float:
    import numpy as np
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def _timed(fn, *args):
    """(fn(*args) with every output ready, seconds)."""
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


def _compile(fn, *args, **jit_kw):
    """AOT-compile ``fn`` for ``args``: (executable, seconds)."""
    import jax
    t0 = time.perf_counter()
    exe = jax.jit(fn, **jit_kw).lower(*args).compile()
    return exe, time.perf_counter() - t0


def _peaks() -> list[int]:
    """Peak bytes in use per device; -1 where the backend keeps no count."""
    import jax
    return [(d.memory_stats() or {}).get("peak_bytes_in_use", -1)
            for d in jax.local_devices()]


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------

def device_phase(n_chips: int) -> dict:
    import importlib.metadata

    import jax

    from repro.chips import chip

    devs = jax.devices()
    d = devs[0]
    check(d.platform == "tpu", f"JAX found no TPU (platform {d.platform!r})")
    check(len(devs) >= n_chips,
          f"{n_chips} chips asked for, JAX found {len(devs)}")
    peaks = chip(d.device_kind)         # unknown kinds raise
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed as a package"
    log(f"[device] {d.device_kind} x{len(devs)}  jax {jax.__version__}  "
        f"libtpu {libtpu}  peaks: {peaks.bf16_flops / 1e12:g} TFLOP/s bf16, "
        f"{peaks.hbm_bw / 1e9:g} GB/s HBM")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


# ---------------------------------------------------------------------------
# plan: the MIREDO path on the compiled kernels
# ---------------------------------------------------------------------------

def plan_scenarios():
    from repro.configs.base import ShapeSpec
    return (ShapeSpec("prefill_2k", seq_len=2048, global_batch=1,
                      kind="prefill"),
            ShapeSpec("decode_b16", seq_len=2048, global_batch=16,
                      kind="decode"))


def _blocks(op) -> str:
    s = op.spec
    if op.kernel == "matmul_int8":
        return (f"{s['m']}x{s['k']}x{s['n']} blocks "
                f"{s['bm']}/{s['bk']}/{s['bn']}")
    if op.kernel == "flash_attention":
        return (f"b{s['b']} lq{s['lq']} lk{s['lk']} h{s['h']} hd{s['hd']} "
                f"blocks {s['bq']}/{s['bk']}")
    return f"q{s['q']} n{s['n']} p{s['p']}"


def plan_phase(cfg, scenarios, *, seed: int, interpret: bool = False,
               per_layer_cap_s: float = 2.0) -> None:
    from repro.core.arch import default_arch
    from repro.core.executor import execute_plan, lower_plan
    from repro.core.frontend import extract_workload
    from repro.core.network import optimize_network

    arch = default_arch()
    seen_kernels = set()
    for spec in scenarios:
        work = extract_workload(cfg, spec)
        t0 = time.perf_counter()
        net = optimize_network(list(work.layers), arch, "miredo",
                               counts=list(work.counts),
                               per_layer_cap_s=per_layer_cap_s, workers=1,
                               use_cache=False)
        solve_s = time.perf_counter() - t0
        plan = lower_plan(cfg, spec, net, arch)
        t0 = time.perf_counter()
        rep = execute_plan(plan, interpret=interpret, seed=seed)
        exec_s = time.perf_counter() - t0
        unique = list({op.key: op for op in plan.ops}.values())
        for op in unique:
            log(f"[plan] {spec.name} {op.kernel:>15} {op.name}: "
                f"{_blocks(op)}  {op.measured_s * 1e3:.4f} ms  "
                f"first call {op.first_call_s:.3f} s  "
                f"rel_err {op.rel_err:.2e}  "
                f"{'ok' if op.numerics_ok else 'FAILED'}")
        log(f"[plan] {spec.name}: {len(work)} layers, {net.n_unique} unique "
            f"MIP solves in {solve_s:.2f} s; {rep.n_unique} unique ops of "
            f"{rep.n_ops}; compile + check "
            f"{sum(op.first_call_s for op in unique):.3f} s, timed runs "
            f"{sum(op.measured_s for op in unique) * 1e3:.4f} ms; execute "
            f"{exec_s:.2f} s wall")
        check(rep.numerics_ok,
              f"{spec.name}: kernel output diverged from its ref oracle "
              f"(max rel err {rep.max_rel_err:.2e})")
        seen_kernels |= {op.kernel for op in plan.ops}
    check(seen_kernels == KERNELS,
          f"kernel families never dispatched: {sorted(KERNELS - seen_kernels)}")


# ---------------------------------------------------------------------------
# serve: prefill + decode through the model's step functions
# ---------------------------------------------------------------------------

def _init_params(cfg, seed: int, **jit_kw):
    import jax
    import jax.numpy as jnp

    from repro.models.transformer import init_model
    return jax.jit(lambda k: init_model(k, cfg, jnp.bfloat16),
                   **jit_kw)(jax.random.PRNGKey(seed))


def _tokens(cfg, seed: int, batch: int, length: int):
    import jax
    import jax.numpy as jnp
    return jax.random.randint(jax.random.PRNGKey(seed + 1), (batch, length),
                              0, cfg.vocab_size, jnp.int32)


def serve(cfg, params, tokens, gen: int, *, shard=None, tag: str):
    """Prefill ``tokens`` then greedily decode ``gen`` tokens; returns the
    prefill's last-position logits and the final caches. Checks logits are
    finite and every decoded position landed in the cache."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.train.steps import (StepConfig, decode_caches,
                                   make_decode_step, make_prefill_step)

    step_cfg = StepConfig(remat=False)
    batch, prompt = tokens.shape
    max_seq = prompt + gen
    prefill, c_s = _compile(make_prefill_step(cfg, step_cfg, shard),
                            params, {"tokens": tokens})
    (logits, caches), p_s = _timed(prefill, params, {"tokens": tokens})
    check(bool(jnp.all(jnp.isfinite(logits))), f"{tag}: prefill logits")
    caches = jax.jit(functools.partial(decode_caches, cfg, batch=batch,
                                       max_seq=max_seq))(caches)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
    decode = jax.jit(make_decode_step(cfg, step_cfg, shard),
                     donate_argnums=(2,))
    finite = jnp.bool_(True)
    times = []
    for _ in range(gen):
        t0 = time.perf_counter()
        step_logits, caches = jax.block_until_ready(
            decode(params, {"tokens": tok}, caches))
        times.append(time.perf_counter() - t0)
        finite = finite & jnp.all(jnp.isfinite(step_logits))
        tok = jnp.argmax(step_logits, -1).astype(jnp.int32)[:, None]
    check(bool(finite), f"{tag}: decode logits")
    kv = caches[1] if cfg.family == "hybrid" else caches
    length = np.asarray(kv.length)
    check(bool(np.all(length == max_seq)),
          f"{tag}: cache length {sorted(set(length.ravel().tolist()))} "
          f"!= {max_seq}")
    check(bool(jnp.any(kv.k[:, :, max_seq - 1] != 0)),
          f"{tag}: the last decode step's keys never reached the cache")
    log(f"[{tag}] prefill {batch}x{prompt}: compile {c_s:.2f} s, run "
        f"{p_s * 1e3:.2f} ms; decode {gen} steps: first (compile + run) "
        f"{times[0]:.2f} s, then {sum(times[1:]) * 1e3 / (gen - 1):.3f} "
        f"ms/step; cache length {max_seq}, last slot written, logits "
        f"finite")
    return logits, caches


def serve_phase(cfg, *, seed: int, batch: int = 4, prompt: int = 1024,
                gen: int = 32) -> None:
    import jax
    import jax.numpy as jnp

    from repro.train.steps import StepConfig, make_prefill_step

    params = _init_params(cfg, seed)
    n = sum(x.size for x in jax.tree.leaves(params))
    log(f"[serve] {cfg.name}: {n / 1e9:.3f} B parameters in bf16")
    tokens = _tokens(cfg, seed, batch, prompt)
    logits, caches = serve(cfg, params, tokens, gen, tag="serve")
    del caches
    # the same prefill with the flash-attention kernel inside the model
    flash_step = make_prefill_step(cfg, StepConfig(remat=False,
                                                   use_flash=True))
    exe, c_s = _compile(flash_step, params, {"tokens": tokens})
    check("tpu_custom_call" in exe.as_text(),
          "flash prefill compiled without its Pallas kernel")
    (flash_logits, _), r_s = _timed(exe, params, {"tokens": tokens})
    rel = _rel(flash_logits, logits)
    agree = float(jnp.mean(jnp.argmax(flash_logits, -1) ==
                           jnp.argmax(logits, -1)))
    log(f"[serve] flash prefill: compile {c_s:.2f} s, run {r_s * 1e3:.2f} "
        f"ms; last-position logits rel L2 {rel:.3e} vs plain prefill "
        f"(tolerance {FLASH_TOL:g}), argmax agreement {agree:.2f}; peak "
        f"bytes {_peaks()}")
    check(rel <= FLASH_TOL, f"flash prefill logits off by {rel:.3e}")


# ---------------------------------------------------------------------------
# sharded: glm4-9b over a 4-chip host
# ---------------------------------------------------------------------------

def sharded_phase(cfg, *, seed: int, prompt: int = 2048, gen: int = 8,
                  cut_layers: int = 2) -> None:
    import gc

    import jax
    import jax.numpy as jnp

    from repro.configs.base import ShapeSpec
    from repro.launch.mesh import make_host_mesh
    from repro.models.transformer import init_model
    from repro.sharding.rules import make_plan
    from repro.train.steps import StepConfig, make_prefill_step

    mesh = make_host_mesh()
    spec = ShapeSpec("serve", seq_len=prompt, global_batch=1, kind="prefill")
    tokens = _tokens(cfg, seed, 1, prompt)

    plan = make_plan(mesh, cfg, spec)
    shapes = jax.eval_shape(lambda k: init_model(k, cfg, jnp.bfloat16),
                            jax.random.PRNGKey(seed))
    total = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(shapes))
    params, i_s = _timed(lambda: _init_params(
        cfg, seed, out_shardings=plan.params_shardings(shapes)))
    held = [0] * len(mesh.devices.flat)
    index = {d: i for i, d in enumerate(mesh.devices.flat)}
    for x in jax.tree.leaves(params):
        for sh in x.addressable_shards:
            held[index[sh.device]] += sh.data.size * sh.data.dtype.itemsize
    log(f"[sharded] {cfg.name}: {total / 1e9:.2f} GB of bf16 parameters "
        f"made in {i_s:.2f} s over mesh {dict(mesh.shape)}; per device "
        f"{[round(h / 1e9, 3) for h in held]} GB")
    check(max(held) < total, "a device holds the whole model")
    serve(cfg, params, tokens, gen, shard=plan.shard_fn(), tag="sharded")
    peaks = _peaks()
    log(f"[sharded] peak bytes in use per device: {peaks}")
    check(max(peaks) < total, "a device's peak reached the whole model")
    del params
    gc.collect()

    # the same widths cut to ``cut_layers``: sharded vs one device
    cut = dataclasses.replace(cfg, n_layers=cut_layers)
    cut_plan = make_plan(mesh, cut, spec)
    step_cfg = StepConfig(remat=False)
    one = _init_params(cut, seed)               # on the default device
    ref, _ = jax.jit(make_prefill_step(cut, step_cfg))(one,
                                                       {"tokens": tokens})
    sharded = jax.device_put(one, cut_plan.params_shardings(one))
    got, _ = jax.jit(make_prefill_step(cut, step_cfg, cut_plan.shard_fn()))(
        sharded, {"tokens": tokens})
    rel = _rel(got, ref)
    log(f"[sharded] {cut.name} cut to {cut_layers} layers: last-position "
        f"logits rel L2 {rel:.3e} sharded vs one device (tolerance "
        f"{SHARD_TOL:g})")
    check(rel <= SHARD_TOL, f"sharded logits off by {rel:.3e}")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: plan + serve on one chip; 4: only the sharded "
                         "glm4-9b phase and its comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.configs import get_config
    from repro.launch.compile_cache import use_compile_cache

    cache = use_compile_cache()
    warm = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    log(f"[cache] compilation cache {cache} ({warm} entries before this run)")
    phase = "device"
    try:
        device = device_phase(args.chips)
        t0 = time.perf_counter()
        if args.chips == 4:
            phase = "sharded"
            sharded_phase(get_config("glm4-9b"), seed=args.seed)
        else:
            zamba = get_config("zamba2-1.2b")
            phase = "plan"
            plan_phase(zamba, plan_scenarios(), seed=args.seed)
            phase = "serve"
            serve_phase(zamba, seed=args.seed)
        log(f"[done] phases passed in {time.perf_counter() - t0:.1f} s")
    except Exception as e:                  # any failure: no result line
        traceback.print_exc()
        print(f"[FAILED] {phase}: {type(e).__name__}: {e}", file=sys.stderr,
              flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
