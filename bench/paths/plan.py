"""One step of the executed MIREDO plan.

Set-up extracts the workload of the traffic's scenario, solves every
unique layer's MIP (``optimize_network``, no solve cache, in this
process) and lowers the plan. Its operands are made from the seed on the
device, in the dtypes the executor feeds (float32; the matmul quantizes
inside): a weight of its own for every call of a matmul op, as each layer
of the model holds its own, and one set of activations per unique op. A
step calls each op of the plan ``count`` times in stream order through
``kernels/*/ops.py``, with the blocks the MIP and the bridge chose.

Correct: after the window, the last output of every unique op is
compared with the plain reference on the same operands: the widest
elementwise gap over the reference's root mean square, for each kernel
family.
"""

from __future__ import annotations

import collections
import hashlib
import time

from bench import common, weights
from bench.model import model_config
from repro.configs.base import ShapeSpec
from repro.core.arch import default_arch
from repro.core.executor import lower_plan
from repro.core.frontend import extract_workload
from repro.core.network import optimize_network
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.matmul_int8.ops import quantized_matmul

METRIC = "plan_step_ms"

#: The number each kernel family's comparison is reported under.
NUMBER = {"matmul_int8": "matmul_err", "flash_attention": "attention_err"}


def digest(plan) -> tuple[str, list[str]]:
    """A short hash of the plan and one line per op: kernel, dims, blocks
    and count, in stream order."""
    lines = [f"{op.kernel} {op.name} "
             + " ".join(f"{k}={v}" for k, v in sorted(op.spec.items()))
             + f" count={op.count}" for op in plan.ops]
    return hashlib.sha256("\n".join(lines).encode()).hexdigest()[:16], lines


class Path:
    def __init__(self, ctx):
        import jax

        t = ctx.traffic
        self.ctx = ctx
        cfg = model_config(ctx.config)
        spec = ShapeSpec(t["scenario"], seq_len=t["seq_len"],
                         global_batch=t["batch"], kind=t["kind"])
        arch = default_arch()
        wl = extract_workload(cfg, spec)
        t0 = time.perf_counter()
        net = optimize_network(list(wl.layers), arch, t["mode"],
                               counts=list(wl.counts),
                               per_layer_cap_s=t["per_layer_cap_s"],
                               workers=1, use_cache=False)
        self.solve_s = time.perf_counter() - t0
        self.plan = lower_plan(cfg, spec, net, arch)
        self.digest, lines = digest(self.plan)
        common.log(f"[plan] digest {self.digest}: {len(self.plan.ops)} ops, "
                   f"{sum(op.count for op in self.plan.ops)} calls a step, "
                   f"solved in {self.solve_s:.3f} s")
        for line in lines:
            common.log(f"[plan]   {line}")
        self.families = sorted({op.kernel for op in self.plan.ops})
        unknown = set(self.families) - set(NUMBER)
        if unknown:
            raise ValueError(f"the plan runs kernels this path cannot "
                             f"check: {sorted(unknown)}")
        self.operands = _operands(self.plan.ops, ctx.seed)
        self.calls = [(op, [self._call(op, a) for a in self.operands[i]])
                      for i, op in enumerate(self.plan.ops)]
        self.out: dict = {}
        jax.block_until_ready(self.issue())      # compiles every shape
        self.out = {}

    def _call(self, op, args):
        s, interp = op.spec, self.ctx.interpret
        if op.kernel == "matmul_int8":
            x, w = args
            blocks = (s["bm"], s["bk"], s["bn"])
            return lambda: quantized_matmul(x, w, block_shapes=blocks,
                                            out_dtype=x.dtype,
                                            interpret=interp)
        q, k, v = args
        return lambda: flash_attention(q, k, v, causal=s["causal"],
                                       block_q=s["bq"], block_k=s["bk"],
                                       interpret=interp)

    def issue(self):
        for op, calls in self.calls:
            for call in calls:
                out = call()
            self.out[op.key] = out
        return out

    def after_window(self):
        pass

    def work(self) -> dict:
        from bench.work import flash_attention, matmul_int8
        kernels = collections.defaultdict(list)
        for op in self.plan.ops:
            s = op.spec
            if op.kernel == "matmul_int8":
                w = matmul_int8.work(s["m"], s["k"], s["n"])
            else:
                w = flash_attention.work(s["b"], s["lq"], s["lk"], s["h"],
                                         s["hd"], s["causal"])
            kernels[op.kernel] += [w] * op.count
        return {"step": None, "kernels": dict(kernels),
                "plan_solve_s": self.solve_s}

    def numbers(self, control: bool = False) -> dict:
        from bench.refs import plan_ops
        worst = {}
        last = {op.key: self.operands[i][-1]
                for i, op in enumerate(self.plan.ops)}
        for op in {op.key: op for op in self.plan.ops}.values():
            args = last[op.key]
            if op.kernel == "matmul_int8":
                ref = plan_ops.matmul(*args)
                got = plan_ops.matmul(*args, prec="int4") if control \
                    else self.out[op.key]
            else:
                causal = op.spec["causal"]
                ref = plan_ops.attention(*args, causal=causal)
                got = plan_ops.attention(*args, causal=causal, prec="high") \
                    if control else self.out[op.key]
            name = NUMBER[op.kernel]
            worst[name] = max(worst.get(name, 0.0), common.worst_rel(got, ref))
        return {k: (v, self.ctx.limits[k]) for k, v in sorted(worst.items())}


def _operands(ops, seed: int) -> list[list[tuple]]:
    """For each op, the arguments of each of its ``count`` calls, made on
    the device from ``seed`` in one jitted call: standard normal
    activations shared by the calls of one unique op, and matmul weights
    (scaled by 0.1) of their own for every call."""
    import jax
    import jax.numpy as jnp

    first = {}
    for i, op in enumerate(ops):
        first.setdefault(op.key, i)

    def make(key):
        acts, ws = {}, []
        for i, op in enumerate(ops):
            s = op.spec
            k = jax.random.fold_in(key, i)
            normal = lambda j, shape: jax.random.normal(
                jax.random.fold_in(k, j), shape, jnp.float32)
            if op.kernel == "matmul_int8":
                if first[op.key] == i:
                    acts[i] = (normal(0, (s["m"], s["k"])),)
                ws.append([0.1 * normal(1 + c, (s["k"], s["n"]))
                           for c in range(op.count)])
            else:
                if first[op.key] == i:
                    acts[i] = tuple(normal(j, (s["b"], s[n], s["h"], s["hd"]))
                                    for j, n in enumerate(("lq", "lk", "lk")))
                ws.append([])
        return acts, ws

    acts, ws = jax.jit(make)(jax.random.PRNGKey(weights.key32(seed, 3)))
    return [[acts[first[op.key]] + (w,) for w in w_op] if w_op
            else [acts[first[op.key]]] * op.count
            for op, w_op in zip(ops, ws)]


def setup(ctx):
    return Path(ctx)
