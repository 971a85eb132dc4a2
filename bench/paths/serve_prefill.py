"""Prefill of one batch of prompts at a time through the program's prefill
step: the time to the first token of a request.

The traffic draws ``prompts`` distinct prompts of ``prompt`` tokens from
the seed; the window prefills them ``batch`` at a time, in turn, with
``StepConfig(use_flash=...)`` as the traffic states.

Correct: for ``check_prompts`` prompts drawn from the seed, the last
position's logits that the window returned for them are compared with the
plain reference's: the widest relative L2 distance of the row. (The gap
of the one served token per prompt does not separate bf16 from float8
over a few prompts, so it is not compared.)
"""

from __future__ import annotations

import numpy as np

from bench import weights
from bench.model import make_params, model_config
from repro.train.steps import StepConfig, make_prefill_step

METRIC = "prefill_ms"


class Path:
    def __init__(self, ctx):
        import jax

        t = ctx.traffic
        self.ctx = ctx
        self.cfg = cfg = model_config(ctx.config)
        self.batch, self.prompt = t["batch"], t["prompt"]
        self.n = t["prompts"] // self.batch
        self.params = make_params(ctx.config, ctx.seed)
        prompts = weights.tokens(ctx.seed, (t["prompts"], self.prompt),
                                 cfg.vocab_size)
        self.groups = [prompts[i * self.batch:(i + 1) * self.batch]
                       for i in range(self.n)]
        self.prompts = prompts
        self.use_flash = t["use_flash"]
        self.step = jax.jit(make_prefill_step(
            cfg, StepConfig(remat=False, use_flash=self.use_flash)))
        self.i = 0
        self.last: dict = {}
        jax.block_until_ready(self.issue())      # compiles the one shape
        self.i, self.last = 0, {}

    def issue(self):
        g = self.i % self.n
        logits, _ = self.step(self.params, {"tokens": self.groups[g]})
        self.last[g] = logits
        self.i += 1
        return logits

    def after_window(self):
        import jax
        while len(self.last) < self.n:
            self.issue()
        jax.block_until_ready(list(self.last.values()))

    def flash_calls(self) -> int:
        """Calls of the flash kernel in one step: one per layer of a dense
        model, which takes the kernel for prompts of 512 or more."""
        if not (self.use_flash and self.prompt >= 512):
            return 0
        return self.cfg.n_layers

    def work(self) -> dict:
        import importlib

        from bench.work import flash_attention
        fam = importlib.import_module(f"bench.work.{self.ctx.config['work']}")
        m = self.ctx.config["model"]
        kernels = {}
        calls = self.flash_calls()
        if calls:
            hd = m.get("head_dim") or m["d_model"] // m["n_heads"]
            one = flash_attention.work(self.batch, self.prompt, self.prompt,
                                       m["n_heads"], hd, True,
                                       m["n_kv_heads"])
            kernels["flash_attention"] = [one] * calls
        return {"step": fam.prefill(m, self.batch, self.prompt),
                "kernels": kernels}

    def free(self):
        self.step = None

    def numbers(self, control: bool = False) -> dict:
        import importlib
        ref = importlib.import_module(
            f"bench.refs.{self.ctx.config['reference']}")
        rng = np.random.default_rng([self.ctx.seed & (2 ** 63 - 1), 7])
        k = min(self.ctx.traffic["check_prompts"], self.n * self.batch)
        rows = np.sort(rng.choice(self.n * self.batch, k, replace=False))
        got = np.concatenate([np.asarray(self.last[g], np.float32)
                              for g in range(self.n)])[rows]
        self.free()
        self.last = {}
        m = self.ctx.config["model"]
        seqs = np.asarray(self.prompts)[rows]
        exact = np.asarray(ref.logits(self.params, m, seqs,
                                      self.prompt - 1))[:, 0]
        if control:
            got = np.asarray(ref.logits(self.params, m, seqs,
                                        self.prompt - 1, "fp8"))[:, 0]
        rel = np.linalg.norm(got - exact, axis=-1) / \
            np.linalg.norm(exact, axis=-1)
        return {"logits_rel": (float(rel.max()),
                               self.ctx.limits["logits_rel"])}


def setup(ctx):
    return Path(ctx)
