"""Greedy decode of a fixed batch through the program's decode step.

Set-up prefills the batch's prompts (``prefill_batch`` at a time) into
caches sized for one turn. The window decodes in turns of ``turn``
tokens: each turn starts from the prefilled caches and the prefill's
greedy token, and feeds every next token on the device.

The restore between turns is exact without a second copy of the KV
cache: a decode step writes only the position at the cache's length
(``prompt`` and after), so zeroing those positions and resetting the
length gives back the prefilled cache bit for bit. If the restore were
not exact, the served tokens would leave the reference's and the check
below would fail. State that every step overwrites (an SSM's) could not
be restored so; such a model's caches are refused in set-up.

Correct: for ``check_sequences`` sequences drawn from the seed, the
served tokens of the last whole turn (the prefill's token and every
decoded one) are compared with the plain reference run over the prompt
and those tokens: the widest gap by which a served token's reference
logit lies below the reference's best (``token_gaps``).
"""

from __future__ import annotations

import functools

import numpy as np

from bench import common, weights
from bench.model import batch_concat, make_params, model_config
from repro.models.attention import KVCache
from repro.train.steps import (StepConfig, decode_caches, make_decode_step,
                               make_prefill_step)

METRIC = "decode_step_ms"


def _is_kv(node) -> bool:
    return isinstance(node, KVCache)


def _restore(caches, prompt: int):
    """The caches as the prefill left them: KV positions from ``prompt``
    on zeroed and every length set to ``prompt``."""
    import jax
    import jax.numpy as jnp

    def kv(c):
        keep = (jnp.arange(c.k.shape[c.k.ndim - 3]) < prompt)[:, None, None]
        zero = lambda t: None if t is None else \
            jnp.where(keep, t, jnp.zeros((), t.dtype))
        return c._replace(k=zero(c.k), v=zero(c.v),
                          length=jnp.full_like(c.length, prompt),
                          k_scale=zero(c.k_scale), v_scale=zero(c.v_scale))

    return jax.tree.map(kv, caches, is_leaf=_is_kv)


class Path:
    def __init__(self, ctx):
        import jax
        import jax.numpy as jnp

        t = ctx.traffic
        self.ctx = ctx
        self.cfg = cfg = model_config(ctx.config)
        self.batch, self.prompt, self.turn = t["batch"], t["prompt"], t["turn"]
        self.params = make_params(ctx.config, ctx.seed)
        self.prompts = weights.tokens(ctx.seed, (self.batch, self.prompt),
                                      cfg.vocab_size)
        prefill = jax.jit(make_prefill_step(
            cfg, StepConfig(remat=False, use_flash=t["prefill_flash"])))
        pb = t["prefill_batch"]
        outs = [prefill(self.params, {"tokens": self.prompts[i:i + pb]})
                for i in range(0, self.batch, pb)]
        logits = jnp.concatenate([o[0] for o in outs], 0)
        caches = batch_concat([o[1] for o in outs])
        del outs
        self.caches = jax.jit(
            lambda c: decode_caches(cfg, c, batch=self.batch,
                                    max_seq=self.prompt + self.turn))(caches)
        del caches
        if not all(map(_is_kv, jax.tree.leaves(self.caches, is_leaf=_is_kv))):
            raise ValueError(f"{cfg.name}: the decode path restores KV "
                             f"caches only")
        self.restore = jax.jit(functools.partial(_restore,
                                                 prompt=self.prompt),
                               donate_argnums=(0,))
        self.first = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
        decode = make_decode_step(cfg, StepConfig(remat=False))

        def step(params, tok, caches):
            out, caches = decode(params, {"tokens": tok}, caches)
            return jnp.argmax(out, -1).astype(jnp.int32)[:, None], caches

        self.step = jax.jit(step, donate_argnums=(2,))
        self.pos = 0
        self.tokens: list = []
        self.whole_turn: list | None = None
        # warm up: a restore and two steps compile everything the window
        # runs; the next issue starts a fresh turn
        for _ in range(2):
            self.issue()
        jax.block_until_ready(self.tok)
        self.pos, self.whole_turn = 0, None

    def issue(self):
        if self.pos == 0:
            with common.span("bench.restore"):
                self.caches = self.restore(self.caches)
            self.tok, self.tokens = self.first, []
        self.tok, self.caches = self.step(self.params, self.tok, self.caches)
        self.tokens.append(self.tok)
        self.pos += 1
        if self.pos == self.turn:
            self.whole_turn, self.pos = self.tokens, 0
        return self.tok

    def after_window(self):
        """Finish the turn under way if none has finished yet: a request
        that ends after the window is late, not missing."""
        import jax
        while self.whole_turn is None:
            self.issue()
        jax.block_until_ready(self.whole_turn[-1])

    def work(self) -> dict:
        import importlib
        fam = importlib.import_module(f"bench.work.{self.ctx.config['work']}")
        # the least context of the turn: its first step attends prompt + 1
        return {"step": fam.decode(self.ctx.config["model"], self.batch,
                                   self.prompt + 1),
                "kernels": {}}

    def _check_rows(self):
        import jax.numpy as jnp
        rng = np.random.default_rng([self.ctx.seed & (2 ** 63 - 1), 7])
        n = self.ctx.traffic["check_sequences"]
        rows = np.sort(rng.choice(self.batch, n, replace=False))
        served = jnp.concatenate([self.first] + self.whole_turn, 1)[rows]
        seqs = jnp.concatenate([self.prompts[rows], served[:, :-1]], 1)
        return np.asarray(seqs), np.asarray(served)

    def free(self):
        for name in ("caches", "tok", "tokens", "whole_turn",
                     "step", "restore"):
            setattr(self, name, None)

    def numbers(self, control: bool = False) -> dict:
        """{name: (value, limit)} against the plain reference; with
        ``control`` the reference in float8 stands in for the program."""
        import importlib
        ref = importlib.import_module(
            f"bench.refs.{self.ctx.config['reference']}")
        seqs, served = self._check_rows()
        self.free()
        m = self.ctx.config["model"]
        start = self.prompt - 1
        exact = np.asarray(ref.logits(self.params, m, seqs, start))
        if control:
            low = np.asarray(ref.logits(self.params, m, seqs, start, "fp8"))
            served = low.argmax(-1)
        gaps = common.token_gaps(exact, served)
        return {"gap": (float(gaps.max()), self.ctx.limits["gap"])}


def setup(ctx):
    return Path(ctx)
